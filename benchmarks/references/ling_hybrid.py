"""Plain reference: the Ling hybrid decoder (``config.json`` of
``inclusionAI/Ling-3.0-flash``, ``model_type`` ``bailing_hybrid``) as a
prefill-only scorer — a pre-norm stack of linear attention with the gated
delta rule (KDA) in five layers of six and latent attention (MLA) in the
sixth, a dense gated MLP in the leading layers and after them sparse-expert
layers with group-limited routing and a shared expert, a final norm and an
untied head. Plain ``jax.numpy`` in float32; no kernel, no cache, no
batching, no chunks, nothing of ``sparkdl_tpu``. Matrix precision is the
caller's (``jax.default_matmul_precision("highest")``).

The equations (``h`` a token's hidden state, ``x`` its normed copy; every
key is the config's; ``d`` = head_dim, ``H`` = num_attention_heads):

  RMSNorm      x · rsqrt(mean(x²) + rms_norm_eps) · g
  block        h ← h + mixer_i(x), x = RMSNorm_1(h); h ← h + ffn_i(x),
               x = RMSNorm_2(h); mixer_i MLA where (i + 1) % layer_group_size
               == 0, else KDA (i the published index); ffn_i dense for the
               first first_k_dense_replace layers held
  KDA          q, k, v = silu(conv(W_{q,k,v} x)), each H × d, conv a causal
               depthwise one of short_conv_kernel_size taps, no bias
               (use_bias false), zeros before the window (linear_silu);
               q ← q / ‖q‖ · d^−½, k ← k / ‖k‖ per head (use_qk_norm);
               β = σ(W_β x), one a head;
               g = kda_lower_bound · σ(e^{A_log} ⊙ (W_f x + dt_bias)) per
               channel, A_log one a head, W_f full rank (no_kda_lora);
               S_t = (I − β_t k_t k_tᵀ) Diag(e^{g_t}) S_{t−1} + β_t k_t v_tᵀ,
               S_0 = 0 (a lax.scan over positions), o_t = S_tᵀ q_t;
               y = W_o [RMSNorm_head(o) ⊙ σ(W_g x)], the norm over each
               head's d (group_norm_size 1), W_g hidden × H, one gate a head
               (gated_attention_proj_granularity_type head_wise)
  MLA          q = W_q x (q_lora_rank null), H × (qk_nope_head_dim +
               qk_rope_head_dim); [c ; k_r] = W_kv_down x (kv_lora_rank +
               qk_rope_head_dim), c ← RMSNorm(c); [k_nope ; v] = W_kv_up c,
               H × (qk_nope_head_dim + v_head_dim); the rotary turns the rope
               dimensions of q and the one k_r, adjacent dimensions
               (2j, 2j + 1) paired (rope_interleave), ω_j = θ^(−2j/r) over
               r = rotary_dim, θ = rope_theta; causal soft-max of
               q · k / √(nope + rope); each head's output times σ(W_g x) of
               its own (head_wise), then W_o
  dense MLP    W_2 (silu(W_1 x) ⊙ W_3 x), width intermediate_size
  expert layer s = σ(W_r x) over all published experts, float32; b the
               selection bias (moe_router_enable_expert_bias); the experts in
               n_group groups, a group's score the sum of its two best s + b,
               the topk_group best groups kept, the num_experts_per_tok best
               s + b among their experts chosen (noaux_tc);
               w = s[chosen] / Σ s[chosen] (norm_topk_prob) ·
               routed_scaling_factor; y = S(x) + Σ_{e chosen, e held} w_e ·
               E_e(x), S and every E_e a gated silu MLP of width
               moe_intermediate_size (moe_shared_expert_intermediate_size),
               where the layer's limit in expert_swiglu_limit_list
               (share_expert_swiglu_limit_list) is not 0 the gate's product
               held under it and the up product within ± it before silu · up
  head         RMSNorm after the last block, then the rows of an untied head

Departures from the published description, each of which changes no result:
MLA's soft-max is computed a block of queries at a time against every key,
masked; each held expert runs over the tokens routed to it, gathered into a
buffer sized from the routing (so a layer is two programs: mixer and router,
then the experts); the expert layer is given ``experts_held`` — it routes
over all experts and computes the held ones' part; the head's logits are made
a block of positions at a time; there is no cache, no generation and no
multi-token prediction (num_nextn_predict_layers, a training objective and a
draft head for generation, is 0 here). Not in the ``config.json`` and so
``assumed`` (the configuration's file says so too): the decay's form under
kda_safe_gate / kda_lower_bound (the floor times a sigmoid, which keeps every
g in (lower, 0)); head_wise for both output gates, KDA's and MLA's; use_qk_norm
as the L2 norm in KDA and nothing in MLA; the clamp's form; the rope
dimensions after the nope ones in a head's query columns.

Weights are made from the seed part by part (``init_embed``, ``init_layer``,
``init_head``), so that a caller never holds more than one layer in float32,
and every drawn value is rounded to bfloat16, the precision the model is
published in. An expert's weights depend on the key and the expert's id
alone, whichever share holds it. ``quant`` is the control's hook: applied to
both operands of every matrix product that the configuration runs in
bfloat16 (the router, the rotary tables, the recurrence's state stay
float32). ``without(s, …)`` makes the faults this model can have: the state
reset at each chunk of 64, the delta rule's removal left out, the decay a
head's mean, routing over all experts, the rotary by halves.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np


def sizes(config):
    """The sizes the passes need, from the configuration's file: every width
    as published; ``layers_held`` the published index of each layer this
    chip holds, in order, ``experts`` the published router width."""
    held = tuple(config["layers_held"])
    if len(held) != config["num_hidden_layers"]:
        raise ValueError(f"{config['num_hidden_layers']} layers, {held}")
    period = config["layer_group_size"]
    routed = config["expert_swiglu_limit_list"]
    shared = config["share_expert_swiglu_limit_list"]
    return SimpleNamespace(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        head_dim=config["head_dim"], taps=config["short_conv_kernel_size"],
        lower=float(config["kda_lower_bound"]),
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
        theta=float(config["rope_theta"]),
        interleave=bool(config["rope_interleave"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        experts=config["published"].get("num_experts",
                                        config["num_experts"]),
        experts_held=tuple(config["experts_held"]),
        groups=config["n_group"], topk_group=config["topk_group"],
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        scaling=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"], vocab=config["vocab_size"],
        layers=len(held), dense_layers=config["first_k_dense_replace"],
        layer_types=tuple("mla" if (i + 1) % period == 0 else "kda"
                          for i in held),
        limits=tuple((float(routed[i]), float(shared[i])) for i in held),
        carry=None, delta=True, decay="channel", grouped=True)


def without(s, what):
    """``s`` with one of the model's mechanisms taken out: ``"carry"`` — the
    KDA state reset to zero at each chunk of 64 positions; ``"delta"`` — the
    removal ``−β k kᵀ`` left out (gated linear attention); ``"decay"`` — each
    channel's decay replaced by its head's mean; ``"groups"`` — the top-k
    over all experts; ``"interleave"`` — the rotary pairing a head's
    halves."""
    changes = {"carry": {"carry": 64}, "delta": {"delta": False},
               "decay": {"decay": "head"}, "groups": {"grouped": False},
               "interleave": {"interleave": False}}
    if what not in changes:
        raise ValueError(f"no mechanism {what!r}")
    return SimpleNamespace(**{**vars(s), **changes[what]})


# -- weights (the `assumed` distributions of the configuration's file) -------

# Laguna's recipe (references/laguna_moe.py), with what this model adds. The
# router N(0, 1/hidden) — its logits N(0, about 1) on the normed input, so
# the sigmoid scores spread — and the selection bias N(0, 0.1²), under the
# scores' spread, so that every group is some token's choice. KDA: the
# decay's logit W_f x + dt_bias with W_f N(0, 1/hidden) and dt_bias U(−12,
# −1) a channel, A_log U(−0.5, 0.5) a head, so that a head's channels forget
# over a position or over tens of thousands (|g| from 1e-5 to 1.3); the taps
# N(0, 1/taps); MLA's queries and its latent's up-projection at QK_SCALE, so
# that the scores, nope and rope parts alike, spread enough that the rotary's
# pairing shows.
OUT_SCALE = {"attn": 0.3, "mlp": 0.25}
QK_SCALE = 2.0
BIAS_SCALE = 0.1


def _published(a):
    """The model is published in bfloat16: a drawn value is rounded to it, so
    the reference's float32 weights and the program's bfloat16 ones are the
    same numbers, and what is compared is the arithmetic."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, fan_in, scale=1.0):
    return _published(jax.random.normal(key, shape, jnp.float32) * (
        scale * fan_in ** -0.5))


def _uniform(key, shape, lo, hi):
    return _published(jax.random.uniform(key, shape, jnp.float32, lo, hi))


def _gain(key, n, lo=0.7, hi=1.3):
    return _uniform(key, (n,), lo, hi)


def _keys(key, names):
    return {name: jax.random.fold_in(key, i) for i, name in enumerate(names)}


def _mlp(key, hidden, width):
    k = _keys(key, ("gate", "up", "down"))
    return {"gate": _normal(k["gate"], (hidden, width), hidden),
            "up": _normal(k["up"], (hidden, width), hidden),
            "down": _normal(k["down"], (width, hidden), width,
                            OUT_SCALE["mlp"])}


def init_embed(key, s):
    return {"embed": _normal(jax.random.fold_in(key, 1000),
                             (s.vocab, s.hidden), 1)}


def init_head(key, s):
    k = _keys(jax.random.fold_in(key, 2000), ("final_norm", "head"))
    return {"final_norm": _gain(k["final_norm"], s.hidden),
            "head": _normal(k["head"], (s.vocab, s.hidden), s.hidden)}


def _kda(key, s):
    k = _keys(key, ("q", "k", "v", "f", "q_taps", "k_taps", "v_taps",
                    "dt_bias", "A_log", "beta", "gate", "o_norm", "out"))
    wide = s.heads * s.head_dim
    return {
        **{name: _normal(k[name], (s.hidden, wide), s.hidden)
           for name in ("q", "k", "v", "f")},
        **{name: _normal(k[name], (wide, s.taps), s.taps)
           for name in ("q_taps", "k_taps", "v_taps")},
        "dt_bias": _uniform(k["dt_bias"], (wide,), -12.0, -1.0),
        "A_log": _uniform(k["A_log"], (s.heads,), -0.5, 0.5),
        "beta": _normal(k["beta"], (s.hidden, s.heads), s.hidden),
        "gate": _normal(k["gate"], (s.hidden, s.heads), s.hidden),
        "o_norm": _gain(k["o_norm"], s.head_dim),
        "out": _normal(k["out"], (wide, s.hidden), wide, OUT_SCALE["attn"])}


def _mla(key, s):
    k = _keys(key, ("q", "kv_down", "kv_norm", "kv_up", "gate", "out"))
    return {
        "q": _normal(k["q"], (s.hidden, s.heads * (s.nope + s.rope)),
                     s.hidden, QK_SCALE),
        "kv_down": _normal(k["kv_down"], (s.hidden, s.kv_rank + s.rope),
                           s.hidden, QK_SCALE),
        "kv_norm": _gain(k["kv_norm"], s.kv_rank),
        "kv_up": _normal(k["kv_up"], (s.kv_rank, s.heads * (s.nope + s.v)),
                         s.kv_rank, QK_SCALE),
        "gate": _normal(k["gate"], (s.hidden, s.heads), s.hidden),
        "out": _normal(k["out"], (s.heads * s.v, s.hidden), s.heads * s.v,
                       OUT_SCALE["attn"])}


def init_layer(key, s, index, kind=None, dense=None, experts_held=None):
    """Held layer ``index`` (it may be traced where ``kind`` and ``dense``
    are given; by default they are position ``index``'s), its expert layer
    holding ``experts_held`` (default: the configuration's)."""
    if kind is None:
        kind, dense = s.layer_types[index], index < s.dense_layers
    held = s.experts_held if experts_held is None else tuple(experts_held)
    k = _keys(jax.random.fold_in(key, index), (
        "operator_norm", "ffn_norm", "mixer", "mlp", "router", "bias",
        "experts", "shared"))
    layer = {"operator_norm": _gain(k["operator_norm"], s.hidden),
             "ffn_norm": _gain(k["ffn_norm"], s.hidden),
             kind: (_kda if kind == "kda" else _mla)(k["mixer"], s)}
    if dense:
        layer["mlp"] = _mlp(k["mlp"], s.hidden, s.dense_width)
        return layer
    layer["moe"] = {
        "router": _normal(k["router"], (s.hidden, s.experts), s.hidden),
        "expert_bias": _normal(k["bias"], (s.experts,), 1, BIAS_SCALE),
        # leaves stacked over the held experts
        "experts": jax.vmap(lambda e: _mlp(
            jax.random.fold_in(k["experts"], e), s.hidden, s.expert_width))(
                jnp.asarray(held, jnp.int32)),
        "shared": _mlp(k["shared"], s.hidden, s.shared_width)}
    return layer


# -- the passes ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(a, b, quant):
    return quant(a) @ quant(b)


def causal_conv(z, taps):
    """z (T, channels), taps (channels, L): c[t] = Σ_j taps[:, j] ⊙
    z[t − (L − 1) + j], zeros before the window."""
    L = taps.shape[1]
    z = jnp.concatenate([jnp.zeros((L - 1, z.shape[1]), z.dtype), z])
    return sum(taps[:, j] * z[j:j + z.shape[0] - L + 1] for j in range(L))


def delta_rule(q, k, v, g, beta, s):
    """The recurrence over one window, a position at a time: q, k, v, g
    (T, H, d), beta (T, H) → o (T, H, d). ``s.carry``: the state reset to
    zero every that many positions; ``s.delta`` False: no removal."""
    H, d = q.shape[1], q.shape[2]

    def step(state, inputs):
        t, q_t, k_t, v_t, g_t, b_t = inputs
        if s.carry is not None:
            state = jnp.where(t % s.carry == 0, 0.0, state)
        state = state * jnp.exp(g_t)[:, :, None]            # (H, d_k, d_v)
        if s.delta:
            state = state - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum(
                "hk,hkv->hv", k_t, state)[:, None, :]
        state = state + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (
        jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o


def kda(p, x, s, quant):
    """The KDA mixer over x (T, hidden), one window."""
    T = x.shape[0]

    def heads(a):
        return a.reshape(T, s.heads, -1)

    def projected(name):
        return jax.nn.silu(causal_conv(_mm(x, p[name], quant),
                                       p[name + "_taps"]))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(heads(projected("q"))) * s.head_dim ** -0.5
    k = unit(heads(projected("k")))
    v = heads(projected("v"))
    beta = jax.nn.sigmoid(_mm(x, p["beta"], quant))
    g = s.lower * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None]
        * heads(_mm(x, p["f"], quant) + p["dt_bias"]))
    if s.decay == "head":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    o = delta_rule(q, k, v, g, beta, s)
    o = rms_norm(o, p["o_norm"], s.eps) * jax.nn.sigmoid(
        _mm(x, p["gate"], quant))[:, :, None]
    return _mm(o.reshape(T, -1), p["out"], quant)


def rotary(x, theta, interleave):
    """x (…, T, r): position t turns pair j — dimensions (2j, 2j + 1) where
    ``interleave``, else (j, j + r/2) — by t · θ^(−2j/r)."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
            x.shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla(p, x, s, quant, block=256):
    """The MLA mixer over x (T, hidden), one window: a masked soft-max, a
    block of ``block`` queries at a time against every key."""
    T = x.shape[0]
    block = block if T % block == 0 else T
    q = jnp.swapaxes(_mm(x, p["q"], quant).reshape(T, s.heads, -1), 0, 1)
    down = _mm(x, p["kv_down"], quant)
    c = rms_norm(down[:, :s.kv_rank], p["kv_norm"], s.eps)
    kv = jnp.swapaxes(_mm(c, p["kv_up"], quant).reshape(T, s.heads, -1), 0, 1)
    q = jnp.concatenate([q[..., :s.nope], rotary(q[..., s.nope:], s.theta,
                                                 s.interleave)], -1)
    k_rope = rotary(down[:, s.kv_rank:], s.theta, s.interleave)
    k = jnp.concatenate([kv[..., :s.nope], jnp.broadcast_to(
        k_rope, (s.heads, T, s.rope))], -1)
    v = kv[..., s.nope:]
    keys = jnp.arange(T)[None, :]

    def one_block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, block, 1)
        scores = jnp.einsum("hqd,hkd->hqk", quant(rows), quant(k)) * (
            s.nope + s.rope) ** -0.5
        seen = keys <= first + jnp.arange(block)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", quant(weights), quant(v))

    out = jax.lax.map(one_block, jnp.arange(0, T, block))   # (n, H, b, dv)
    out = jnp.transpose(out, (1, 0, 2, 3)).reshape(s.heads, T, -1)
    out = out * jnp.swapaxes(jax.nn.sigmoid(_mm(x, p["gate"], quant)),
                             0, 1)[:, :, None]
    return _mm(jnp.swapaxes(out, 0, 1).reshape(T, -1), p["out"], quant)


def gated_mlp(p, x, quant, limit=0.0):
    gate, up = _mm(x, p["gate"], quant), _mm(x, p["up"], quant)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _mm(jax.nn.silu(gate) * up, p["down"], quant)


def route(p, x, s):
    """(chosen expert ids (N, k), their weights (N, k)), float32."""
    scores = jax.nn.sigmoid(x @ p["router"])
    choice = scores + p["expert_bias"]
    if s.grouped:
        grouped = choice.reshape(x.shape[0], s.groups, -1)
        best = jnp.sort(grouped, -1)[..., -2:].sum(-1)       # (N, groups)
        rank = jnp.argsort(jnp.argsort(-best, -1), -1)
        choice = jnp.where((rank < s.topk_group)[..., None], grouped,
                           -jnp.inf).reshape(choice.shape)
    _, chosen = jax.lax.top_k(choice, s.top_k)
    top = jnp.take_along_axis(scores, chosen, -1)
    if s.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, top * s.scaling


def routed_part(experts, x, chosen, weights, s, quant, limit=0.0,
                experts_held=None, capacity=None):
    """Σ over the chosen experts that are held: each held expert over the
    tokens routed to it, gathered ``capacity`` at a time (default: all
    tokens), their results weighed and added back."""
    held = s.experts_held if experts_held is None else tuple(experts_held)
    N = x.shape[0]
    capacity = N if capacity is None else capacity

    def one(y, expert):
        params, expert_id = expert
        w = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), -1)
        (rows,) = jnp.nonzero(w != 0, size=capacity, fill_value=N)
        out = gated_mlp(params, x[jnp.minimum(rows, N - 1)], quant, limit)
        return y.at[rows].add(w[jnp.minimum(rows, N - 1), None] * out,
                              mode="drop"), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (experts, jnp.asarray(held, jnp.int32)))
    return y


def expert_layer(p, x, s, quant, limits=(0.0, 0.0), experts_held=None):
    """The routed part and the shared expert — which every share of the
    experts computes alike. Returns (y, chosen ids)."""
    chosen, weights = route(p, x, s)
    y = routed_part(p["experts"], x, chosen, weights, s, quant, limits[0],
                    experts_held)
    return y + gated_mlp(p["shared"], x, quant, limits[1]), chosen


def mixer_forward(layer, h, s, kind, quant=None):
    """The first half of a block over windows h (B, T, hidden): (h, the
    normed input of the ffn (B·T, hidden))."""
    quant = quant or (lambda a: a)
    B, T, _ = h.shape
    u = rms_norm(h, layer["operator_norm"], s.eps)
    mix = kda if kind == "kda" else mla
    h = h + jnp.stack([mix(layer[kind], u[b], s, quant) for b in range(B)])
    return h, rms_norm(h, layer["ffn_norm"], s.eps).reshape(B * T, -1)


def layer_forward(layer, h, s, kind, quant=None, limits=(0.0, 0.0),
                  experts_held=None):
    """One pre-norm block over windows h (B, T, hidden), every held expert
    over all tokens. Returns (h, chosen expert ids (B, T, k), or None for a
    dense layer)."""
    quant = quant or (lambda a: a)
    B, T, _ = h.shape
    h, x = mixer_forward(layer, h, s, kind, quant)
    if "mlp" in layer:
        return h + gated_mlp(layer["mlp"], x, quant).reshape(B, T, -1), None
    m, chosen = expert_layer(layer["moe"], x, s, quant, limits, experts_held)
    return h + m.reshape(B, T, -1), chosen.reshape(B, T, -1)


def head_forward(head, h, tokens, s, quant=None, block=2048):
    """(pooled (B, hidden): the mean over positions of the final-norm state;
    logprobs (B, T): log p(x[t+1] | x[≤t]) over the vocabulary, the last 0),
    a window and ``block`` positions at a time."""
    quant = quant or (lambda a: a)
    x = rms_norm(h, head["final_norm"], s.eps)
    B, T, _ = x.shape
    block = block if T % block == 0 else T
    following = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], 1)

    def some(args):
        x, ids = args
        logp = jax.nn.log_softmax(_mm(x, head["head"].T, quant), -1)
        return jnp.take_along_axis(logp, ids[:, None], -1)[:, 0]

    logprobs = jax.lax.map(some, (x.reshape(B * T // block, block, -1),
                                  following.reshape(-1, block)))
    return jnp.mean(x, 1), logprobs.reshape(B, T).at[:, -1].set(0.0)


def forward(key, s, tokens, quant=None):
    """The whole pass over windows ``tokens`` (B, T) int32, the weights made
    from ``key`` one part at a time and dropped after use. An expert layer
    is two programs — the mixer and the router, then the held experts over
    the tokens routed to each, gathered into a buffer of the launch's most
    (rounded up to a power of two, so that few buffers are compiled) — so
    that no expert multiplies a token it was not chosen by. Returns host
    ``(pooled, logprobs, chosen)``, ``chosen`` a list over the expert layers
    of (B, T, k) expert ids."""
    quant_ = quant or (lambda a: a)
    h = jax.jit(lambda k, t: init_embed(k, s)["embed"][t])(key, tokens)
    B, T = tokens.shape

    def mixer_router(k, h, i, kind, dense):
        layer = init_layer(k, s, i, kind, dense)
        h, x = mixer_forward(layer, h, s, kind, quant)
        if dense:
            return h + gated_mlp(layer["mlp"], x, quant_).reshape(
                h.shape), None, None, None
        chosen, weights = route(layer["moe"], x, s)
        return h, x, chosen, weights

    def experts_part(k, h, x, chosen, weights, i, kind, limits, capacity):
        p = init_layer(k, s, i, kind, False)["moe"]
        y = routed_part(p["experts"], x, chosen, weights, s, quant_,
                        limits[0], capacity=capacity)
        return h + (y + gated_mlp(p["shared"], x, quant_, limits[1])
                    ).reshape(h.shape)

    mixer_router = jax.jit(mixer_router, static_argnums=(3, 4))
    experts_part = jax.jit(experts_part, static_argnums=(6, 7, 8))
    held = np.asarray(s.experts_held)
    out = []
    for index in range(s.layers):
        kind, dense = s.layer_types[index], index < s.dense_layers
        h, x, chosen, weights = mixer_router(key, h, index, kind, dense)
        if dense:
            continue
        ids = np.asarray(jax.device_get(chosen))
        most = max(int(np.bincount(ids.ravel(), minlength=s.experts)[
            held].max()), 1)
        h = experts_part(key, h, x, chosen, weights, index, kind,
                         s.limits[index],
                         min(max(512, 1 << (most - 1).bit_length()), B * T))
        out.append(ids.reshape(B, T, -1))
    pooled, logprobs = jax.jit(
        lambda k, h, t: head_forward(init_head(k, s), h, t, s, quant))(
            key, h, tokens)
    return jax.device_get(pooled), jax.device_get(logprobs), out
