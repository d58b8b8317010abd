"""Plain reference: the openPangu-Ultra-MoE decoder (config.json of
``FreedomIntelligence/openPangu-Ultra-MoE-718B``) as a prefill-only scorer —
multi-head latent attention, sandwich norms, a sigmoid-routed sparse-expert
layer beside one shared expert, a final norm and the head over a slice of the
vocabulary. Plain ``jax.numpy`` in float32; no kernel, no batching, nothing
of ``sparkdl_tpu``. Matrix precision is the caller's
(``jax.default_matmul_precision("highest")``).

The equations (``h`` a token's hidden state):

  RMSNorm      x · rsqrt(mean(x²) + eps) · g
  attention    cq = RMSNorm(W_dq h);  q = W_uq cq → heads × (nope + rope)
               [ckv ; k_r] = W_dkv h;  ckv ← RMSNorm(ckv)
               [k_nope ; v] = W_ukv ckv → heads × (nope + v);  k_r one head
               shared by all; rotary on q_rope and k_r; causal softmax of
               q·kᵀ / √(nope + rope); W_o over the heads' concatenated v
  gated MLP    W_down (silu(W_gate x) ⊙ W_up x)
  expert layer s = sigmoid(W_r x) over all published experts; the top-k; their
               scores over their sum, times the scaling factor;
               y = shared(x) + Σ_{e chosen, e held} w_e · expert_e(x)
  block        h ← h + N_post_attn(attn(N_in(h)))
               h ← h + N_post_mlp(mlp(N_pre_mlp(h)))

The layer is given ``experts_held``: it routes over all experts, computes
every held expert for every token and masks by the routing; what absent
experts would add is left out. With every expert held it is the uncut layer.

Weights are made from the seed part by part (``init_embed``, ``init_layer``,
``init_head``), so that a caller never holds more than one layer in float32;
an expert's weights depend on the key and the expert's id alone, whichever
share holds it. ``quant`` is the control's hook: applied to both operands of
every matrix product that the configuration runs in bfloat16 (the router
stays float32, as the configuration states).
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp


def sizes(config):
    """The sizes the passes need, from the configuration's file: every width
    as published; ``layers`` / ``dense_layers``, ``experts_held`` and ``vocab``
    are what this chip holds, ``experts`` the published router width."""
    return SimpleNamespace(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"], dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        scaling=config["routed_scaling_factor"],
        shared=config["n_shared_experts"], eps=config["rms_norm_eps"],
        theta=config["rope_theta"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"])


# -- weights (the `assumed` distributions of the configuration's file) -------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _gain(key, n, lo, hi):
    return jax.random.uniform(key, (n,), jnp.float32, lo, hi)


def _keys(key, names):
    return {name: jax.random.fold_in(key, i) for i, name in enumerate(names)}


def _mlp(key, hidden, width):
    k = _keys(key, ("gate", "up", "down"))
    return {"gate": _normal(k["gate"], (hidden, width), hidden),
            "up": _normal(k["up"], (hidden, width), hidden),
            "down": _normal(k["down"], (width, hidden), width)}


def init_embed(key, s):
    return {"embed": jax.random.normal(jax.random.fold_in(key, 1000),
                                       (s.vocab, s.hidden), jnp.float32)}


def init_head(key, s):
    k = _keys(jax.random.fold_in(key, 2000), ("norm", "head"))
    return {"final_norm": _gain(k["norm"], s.hidden, 0.7, 1.3),
            "head": _normal(k["head"], (s.vocab, s.hidden), s.hidden)}


def init_layer(key, s, index, dense, experts_held=None):
    """Layer ``index`` (may be traced): a dense layer, else an expert layer
    holding ``experts_held`` (default: the configuration's)."""
    held = s.experts_held if experts_held is None else tuple(experts_held)
    k = _keys(jax.random.fold_in(key, index), (
        "input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
        "q_down", "q_norm", "q_up", "kv_down", "kv_norm", "kv_up", "out",
        "mlp", "router", "shared", "experts"))
    qk = s.nope + s.rope
    layer = {
        "input_norm": _gain(k["input_norm"], s.hidden, 0.7, 1.3),
        "post_attn_norm": _gain(k["post_attn_norm"], s.hidden, 0.35, 0.65),
        "pre_mlp_norm": _gain(k["pre_mlp_norm"], s.hidden, 0.7, 1.3),
        "post_mlp_norm": _gain(k["post_mlp_norm"], s.hidden, 0.35, 0.65),
        "attn": {
            "q_down": _normal(k["q_down"], (s.hidden, s.q_rank), s.hidden),
            "q_norm": _gain(k["q_norm"], s.q_rank, 0.7, 1.3),
            "q_up": _normal(k["q_up"], (s.q_rank, s.heads * qk), s.q_rank),
            "kv_down": _normal(k["kv_down"], (s.hidden, s.kv_rank + s.rope),
                               s.hidden),
            "kv_norm": _gain(k["kv_norm"], s.kv_rank, 0.7, 1.3),
            "kv_up": _normal(k["kv_up"],
                             (s.kv_rank, s.heads * (s.nope + s.v)),
                             s.kv_rank),
            "out": _normal(k["out"], (s.heads * s.v, s.hidden),
                           s.heads * s.v),
        },
    }
    if dense:
        layer["mlp"] = _mlp(k["mlp"], s.hidden, s.dense_width)
        return layer
    experts = jax.vmap(lambda e: _mlp(jax.random.fold_in(k["experts"], e),
                                      s.hidden, s.expert_width))(
        jnp.asarray(held, jnp.int32))
    layer["moe"] = {
        "router": _normal(k["router"], (s.hidden, s.experts), s.hidden),
        "shared": _mlp(k["shared"], s.hidden, s.shared * s.expert_width),
        "experts": experts,          # leaves stacked over the held experts
    }
    return layer


# -- the passes ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rotary(x, theta):
    """x (..., T, rope): position t turns the pair (x[i], x[i + rope/2]) by
    t · theta^(−2i/rope) — the two halves pair up (`assumed`)."""
    half = x.shape[-1] // 2
    t = jnp.arange(x.shape[-2], dtype=jnp.float32)
    angle = t[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32)
                                   / half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mm(a, b, quant):
    return quant(a) @ quant(b)


def attention(p, x, s, quant, block=512):
    """x (T, hidden), one window: masked dense softmax over blocks of
    queries."""
    T = x.shape[0]
    cq = rms_norm(_mm(x, p["q_down"], quant), p["q_norm"], s.eps)
    q = _mm(cq, p["q_up"], quant).reshape(T, s.heads, s.nope + s.rope)
    down = _mm(x, p["kv_down"], quant)
    ckv = rms_norm(down[:, :s.kv_rank], p["kv_norm"], s.eps)
    k_rope = rotary(down[:, s.kv_rank:], s.theta)                # (T, rope)
    kv = _mm(ckv, p["kv_up"], quant).reshape(T, s.heads, s.nope + s.v)
    q = jnp.swapaxes(q, 0, 1)                                    # (H, T, ·)
    q = jnp.concatenate([q[..., :s.nope], rotary(q[..., s.nope:], s.theta)],
                        -1)
    k = jnp.concatenate([jnp.swapaxes(kv[..., :s.nope], 0, 1),
                         jnp.broadcast_to(k_rope, (s.heads, T, s.rope))], -1)
    v = jnp.swapaxes(kv[..., s.nope:], 0, 1)
    scale = (s.nope + s.rope) ** -0.5
    out = []
    for lo in range(0, T, block):
        rows = jnp.arange(lo, min(lo + block, T))
        scores = jnp.einsum("hqd,hkd->hqk", quant(q[:, lo:lo + block]),
                            quant(k)) * scale
        scores = jnp.where(jnp.arange(T)[None, :] <= rows[:, None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("hqk,hkd->hqd",
                              quant(jax.nn.softmax(scores, -1)), quant(v)))
    out = jnp.swapaxes(jnp.concatenate(out, 1), 0, 1).reshape(T, -1)
    return _mm(out, p["out"], quant)


def gated_mlp(p, x, quant):
    return _mm(jax.nn.silu(_mm(x, p["gate"], quant)) * _mm(x, p["up"], quant),
               p["down"], quant)


def route(router, x, s):
    """(chosen expert ids (N, k), their weights (N, k)), float32."""
    scores = jax.nn.sigmoid(x @ router)
    top, chosen = jax.lax.top_k(scores, s.top_k)
    if s.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, top * s.scaling


def routed_part(p, x, s, quant, experts_held=None):
    """Σ over the chosen experts that are held; every held expert is computed
    for every token and masked by the routing. Returns (y, chosen ids)."""
    held = s.experts_held if experts_held is None else tuple(experts_held)
    chosen, weights = route(p["router"], x, s)

    def one(y, expert):
        params, expert_id = expert
        w = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), -1)
        return y + w[:, None] * gated_mlp(params, x, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["experts"], jnp.asarray(held, jnp.int32)))
    return y, chosen


def expert_layer(p, x, s, quant, experts_held=None):
    routed, chosen = routed_part(p, x, s, quant, experts_held)
    return gated_mlp(p["shared"], x, quant) + routed, chosen


def layer_forward(layer, h, s, quant=None, experts_held=None):
    """One block over windows h (B, T, hidden). Returns (h, chosen expert ids
    (B, T, k) of an expert layer, else None)."""
    quant = quant or (lambda a: a)
    B, T, _ = h.shape
    x = rms_norm(h, layer["input_norm"], s.eps)
    a = jnp.stack([attention(layer["attn"], x[b], s, quant)
                   for b in range(B)])
    h = h + rms_norm(a, layer["post_attn_norm"], s.eps)
    x = rms_norm(h, layer["pre_mlp_norm"], s.eps).reshape(B * T, -1)
    chosen = None
    if "moe" in layer:
        m, chosen = expert_layer(layer["moe"], x, s, quant, experts_held)
        chosen = chosen.reshape(B, T, -1)
    else:
        m = gated_mlp(layer["mlp"], x, quant)
    return h + rms_norm(m.reshape(B, T, -1), layer["post_mlp_norm"],
                        s.eps), chosen


def head_forward(head, h, tokens, s, quant=None):
    """(pooled (B, hidden): the mean over positions of the final-norm state;
    logprobs (B, T): log p(x[t+1] | x[≤t]) over the slice, the last 0)."""
    quant = quant or (lambda a: a)
    x = rms_norm(h, head["final_norm"], s.eps)
    logp = jax.nn.log_softmax(_mm(x, head["head"].T, quant), -1)
    nxt = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(x, 1), jnp.pad(nxt, ((0, 0), (0, 1)))


def forward(key, s, tokens, quant=None):
    """The whole pass over windows ``tokens`` (B, T) int32, the weights made
    from ``key`` one part at a time and dropped after use. Returns host
    ``(pooled, logprobs, chosen)``, ``chosen`` a list over the expert layers
    of (B, T, k) expert ids."""
    h = jax.jit(lambda k, t: init_embed(k, s)["embed"][t])(key, tokens)
    # init and pass in one program: a layer's float32 weights live only
    # inside it; one compile for the dense layers, one for the expert layers
    step = jax.jit(lambda k, h, i, dense: layer_forward(
        init_layer(k, s, i, dense), h, s, quant), static_argnums=3)
    chosen = []
    for index in range(s.layers):
        h, ids = step(key, h, index, index < s.dense_layers)
        if ids is not None:
            chosen.append(jax.device_get(ids))
    pooled, logprobs = jax.jit(
        lambda k, h, t: head_forward(init_head(k, s), h, t, s, quant))(
            key, h, tokens)
    return (jax.device_get(pooled), jax.device_get(logprobs),
            chosen)
