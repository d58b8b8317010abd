"""Plain reference: the Laguna decoder (``config.json`` of
``poolside/Laguna-XS.2``, ``model_type`` ``laguna``) as a prefill-only scorer
— a pre-norm stack of gated grouped-query attention, full layers and
sliding-window layers with their own query heads and rotaries, a dense gated
MLP in the leading layer and sparse-expert layers with a shared expert after
it, a final norm and an untied head. Plain ``jax.numpy`` in float32; no
kernel, no cache, no batching, nothing of ``sparkdl_tpu``. Matrix precision is
the caller's (``jax.default_matmul_precision("highest")``).

The equations (``h`` a token's hidden state, ``u`` and ``x`` its normed
copies; every key is the config's):

  RMSNorm      x · rsqrt(mean(x²) + rms_norm_eps) · g
  block        h ← h + W_o gate(attn_i(u)), u = RMSNorm_1(h);
               h ← h + ffn_i(x), x = RMSNorm_2(h); attn_i by layer_types[i],
               ffn_i by mlp_layer_types[i]
  attention    q = W_q u → num_attention_heads_per_layer[i] heads × 128;
               k = W_k u, v = W_v u → num_key_value_heads × 128 (no bias, no
               norm per head); rotary on q and k by the layer's kind
               (rope_parameters), over the first partial_rotary_factor ·
               head_dim dimensions of a head, a turned part's two halves
               paired, the rest passed through:
                 sliding_attention  ω_j = θ^(−2j/r) over r = 128, a = 1
                 full_attention     YaRN over r = 64: ω_j = θ^(−2j/r) ·
                                    ((1 − ρ_j) + ρ_j / factor),
                                    ρ_j = clip((j − low) / (high − low), 0, 1),
                                    low = ⌊dim(beta_fast)⌋, high = ⌈dim(beta_slow)⌉,
                                    dim(n) = r · ln(original_max_position_embeddings
                                    / (2π n)) / (2 ln θ), both clipped to [0, r − 1];
                                    a = attention_factor on cos and sin
               scores q · k / √128, soft-max over keys j ≤ t and, in a sliding
               layer, j > t − sliding_window; query head a reads key head
               a // (heads / kv_heads)
  gate         (gating) each head's output times σ(u W_g)_head, one number a
               head and token, W_g hidden × heads, before W_o
  dense MLP    (mlp_layer_types "dense") W_2 (silu(W_1 x) ⊙ W_3 x), width
               intermediate_size
  expert layer (mlp_layer_types "sparse") s = σ(W_r x) over all num_experts;
               the num_experts_per_tok largest; w = s[chosen] / Σ s[chosen]
               (norm_topk_prob) · moe_routed_scaling_factor;
               y = S(x) + Σ_{e chosen, e held} w_e · E_e(x), S and every E_e a
               gated silu MLP (widths shared_expert_intermediate_size,
               moe_intermediate_size); the weights multiply the experts'
               outputs (moe_apply_router_weight_on_input false)
  head         RMSNorm after the last block, then the rows of an untied head

Departures from the published description, each of which changes no result:
attention is a masked dense soft-max (the causal triangle and the band as a
mask over all keys), a key head's block of query heads and a block of queries
at a time; keys and values are repeated to the query heads by broadcasting;
the expert layer is given ``experts_held`` — it routes over all experts,
computes every held expert for every token and masks by the routing (with
every expert held, as in the benchmark's configuration, it is the uncut
layer); the head's logits are made a block of positions at a time; there is
no cache and no generation. Not in the ``config.json`` and so ``assumed``
(the configuration's file says so too): ``gating`` is the per-head sigmoid
gate of gated attention, from the normed input (the family's other row
writes it ``"per-head"``; the published count, 33,442,596,864, is per-head's);
the MLPs' activation is silu (no ``hidden_act``; the count needs three
matrices an MLP); the router's scores are sigmoids, renormalised, scaled,
with no selection bias (no ``scoring_func``, no bias key); no norm per head;
rope by halves within the turned part.

Weights are made from the seed part by part (``init_embed``, ``init_layer``,
``init_head``), so that a caller never holds more than one layer in float32,
and every drawn value is rounded to bfloat16, the precision the model is
published in. An expert's weights depend on the key and the expert's id
alone, whichever share holds it. ``quant`` is the control's hook: applied to
both operands of every matrix product that the configuration runs in
bfloat16 (the router and the rotary tables stay float32). ``without(s, …)``
makes the faults this model can have: the shared expert left out, the gate
taken as 1, the full layers' rotary over the whole head, the span left out
of the sliding layers.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp


def sizes(config):
    """The sizes the passes need, from the configuration's file: every width
    as published; ``layers`` / ``layer_types`` / ``heads`` are what this chip
    holds, ``experts`` the published router width. ``heads`` is each kind's
    query heads (the config gives them layer by layer)."""
    kinds = tuple(config["layer_types"])
    mlp = tuple(config["mlp_layer_types"])
    dense = mlp.count("dense")
    if mlp != ("dense",) * dense + ("sparse",) * (len(mlp) - dense):
        raise ValueError("dense layers lead the sparse ones in this family")
    heads = {}
    for kind, n in zip(kinds, config["num_attention_heads_per_layer"]):
        if heads.setdefault(kind, n) != n:
            raise ValueError(f"{kind} layers with {heads[kind]} and {n} heads")
    return SimpleNamespace(
        hidden=config["hidden_size"], heads=heads,
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        experts=config["num_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        scaling=config["moe_routed_scaling_factor"],
        eps=config["rms_norm_eps"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"], dense_layers=dense,
        layer_types=kinds, span=config["sliding_window"],
        gate=bool(config["gating"]), shared=True,
        rope={kind: dict(p) for kind, p in config["rope_parameters"].items()
              if isinstance(p, dict)})


def without(s, what):
    """``s`` with one of the model's mechanisms taken out: ``"shared"`` — the
    expert layers without their shared expert; ``"gate"`` — σ taken as 1;
    ``"rotary_part"`` — the full layers turn all of a head; ``"span"`` — the
    sliding layers read every earlier key."""
    if what == "shared":
        return SimpleNamespace(**{**vars(s), "shared": False})
    if what == "gate":
        return SimpleNamespace(**{**vars(s), "gate": False})
    if what == "rotary_part":
        return SimpleNamespace(**{**vars(s), "rope": {
            kind: dict(p, partial_rotary_factor=1)
            for kind, p in s.rope.items()}})
    if what == "span":
        return SimpleNamespace(**{**vars(s), "span": None})
    raise ValueError(f"no mechanism {what!r}")


# -- weights (the `assumed` distributions of the configuration's file) -------

# Mellum's recipe (references/mellum2_moe.py, PERF.md §6), with what this
# model adds: the gate's W_g N(0, 1/hidden), so that its logits are N(0,
# about 1) on the normed input and a head is neither shut nor wide open;
# every MLP's W_2 — the routed experts', the shared expert's and the dense
# layer's — at the one scale, so that the shared expert writes as small as
# the routed ones' weighted sum (2.5 × weights that add up to one).
OUT_SCALE = {"attn": 0.3, "mlp": 0.25}
QK_SCALE = 2.0


def _published(a):
    """The model is published in bfloat16: a drawn value is rounded to it, so
    the reference's float32 weights and the program's bfloat16 ones are the
    same numbers, and what is compared is the arithmetic."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, fan_in, scale=1.0):
    return _published(jax.random.normal(key, shape, jnp.float32) * (
        scale * fan_in ** -0.5))


def _gain(key, n, lo, hi):
    return _published(jax.random.uniform(key, (n,), jnp.float32, lo, hi))


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
    return {"final_norm": _gain(k["final_norm"], s.hidden, 0.7, 1.3),
            "head": _normal(k["head"], (s.vocab, s.hidden), s.hidden)}


def init_layer(key, s, index, kind=None, dense=None, experts_held=None):
    """Layer ``index`` (it may be traced where ``kind`` and ``dense`` are
    given; by default they are position ``index``'s), its expert layer
    holding ``experts_held`` (default: the configuration's)."""
    if kind is None:
        kind, dense = s.layer_types[index], index < s.dense_layers
    held = s.experts_held if experts_held is None else tuple(experts_held)
    k = _keys(jax.random.fold_in(key, index), (
        "operator_norm", "ffn_norm", "q", "k", "v", "o", "gate", "mlp",
        "router", "experts", "shared"))
    heads = s.heads[kind]
    wide, narrow = heads * s.head_dim, s.kv_heads * s.head_dim
    layer = {
        "operator_norm": _gain(k["operator_norm"], s.hidden, 0.7, 1.3),
        "ffn_norm": _gain(k["ffn_norm"], s.hidden, 0.7, 1.3),
        "attn": {
            "q": _normal(k["q"], (s.hidden, wide), s.hidden, QK_SCALE),
            "k": _normal(k["k"], (s.hidden, narrow), s.hidden, QK_SCALE),
            "v": _normal(k["v"], (s.hidden, narrow), s.hidden),
            "gate": _normal(k["gate"], (s.hidden, heads), s.hidden),
            "out": _normal(k["o"], (wide, s.hidden), wide,
                           OUT_SCALE["attn"])}}
    if dense:
        layer["mlp"] = _mlp(k["mlp"], s.hidden, s.dense_width)
        return layer
    layer["moe"] = {
        "router": _normal(k["router"], (s.hidden, s.experts), s.hidden),
        # leaves stacked over the held experts
        "experts": jax.vmap(lambda e: _mlp(
            jax.random.fold_in(k["experts"], e), s.hidden, s.expert_width))(
                jnp.asarray(held, jnp.int32)),
        "shared": _mlp(k["shared"], s.hidden, s.shared_width)}
    return layer


# -- the passes ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_table(p, head_dim):
    """(inverse frequencies (r / 2,), amplitude) of one kind's entry of
    ``rope_parameters``, ``r`` the dimensions of a head that turn."""
    turned = int(head_dim * p.get("partial_rotary_factor", 1))
    half = turned // 2
    theta = float(p["rope_theta"])
    plain = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if p["rope_type"] == "default":
        return plain, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {p['rope_type']!r}")

    def dimension(turns):
        return turned * math.log(p["original_max_position_embeddings"] / (
            2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(dimension(p["beta_fast"])), 0)
    high = min(math.ceil(dimension(p["beta_slow"])), turned - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * ((1.0 - ramp) + ramp / p["factor"]),
            float(p["attention_factor"]))


def rotary(x, frequencies, amplitude):
    """x (..., T, d): position t turns the pair (x[i], x[i + r/2]) of the
    first r = 2 · len(frequencies) dimensions by t · frequencies[i] — the
    turned part's two halves pair up (`assumed`) — with the amplitude on cos
    and sin; dimensions r … d − 1 pass through."""
    half = frequencies.shape[0]
    t = jnp.arange(x.shape[-2], dtype=jnp.float32)
    angle = t[:, None] * frequencies
    cos, sin = amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], -1)


def _mm(a, b, quant):
    return quant(a) @ quant(b)


def attention(p, x, s, kind, quant, block=512):
    """x (T, hidden), one window: a masked dense soft-max over all keys, one
    key head's query heads and ``block`` queries at a time; the gate on each
    head's output where ``s`` has it."""
    T = x.shape[0]
    heads = p["q"].shape[1] // s.head_dim
    group = heads // s.kv_heads
    block = block if T % block == 0 else T
    span = s.span if kind == "sliding_attention" else None

    def split(a):                           # (T, n · d) → (n, T, d)
        return jnp.swapaxes(a.reshape(T, -1, s.head_dim), 0, 1)

    table = rope_table(s.rope[kind], s.head_dim)
    q = rotary(split(_mm(x, p["q"], quant)), *table).reshape(
        s.kv_heads, group, T // block, block, s.head_dim)
    k = rotary(split(_mm(x, p["k"], quant)), *table)
    v = split(_mm(x, p["v"], quant))
    keys = jnp.arange(T)[None, :]

    def one_key_head(args):
        q, k, v = args                      # (group, blocks, block, d), (T, d)

        def one_block(args):
            q, first = args                 # (group, block, d)
            rows = first + jnp.arange(block)[:, None]
            scores = jnp.einsum("rqd,kd->rqk", quant(q), quant(k)) \
                * s.head_dim ** -0.5
            seen = keys <= rows
            if span is not None:
                seen &= keys > rows - span
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("rqk,kd->rqd", quant(weights), quant(v))

        out = jax.lax.map(one_block, (jnp.swapaxes(q, 0, 1),
                                      jnp.arange(0, T, block)))
        return jnp.swapaxes(out, 0, 1).reshape(group, T, -1)

    out = jax.lax.map(one_key_head, (q, k, v)).reshape(heads, T, -1)
    if s.gate:
        out = out * jnp.swapaxes(jax.nn.sigmoid(_mm(x, p["gate"], quant)),
                                 0, 1)[:, :, None]
    return _mm(jnp.swapaxes(out, 0, 1).reshape(T, -1), p["out"], quant)


def gated_mlp(p, x, quant):
    return _mm(jax.nn.silu(_mm(x, p["gate"], quant)) * _mm(x, p["up"], quant),
               p["down"], quant)


def route(p, x, s):
    """(chosen expert ids (N, k), their weights (N, k)), float32."""
    top, chosen = jax.lax.top_k(jax.nn.sigmoid(x @ p["router"]), s.top_k)
    if s.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, top * s.scaling


def routed_part(p, x, s, quant, experts_held=None):
    """Σ over the chosen experts that are held; every held expert is computed
    for every token and masked by the routing. Returns (y, chosen ids)."""
    held = s.experts_held if experts_held is None else tuple(experts_held)
    chosen, weights = route(p, x, s)

    def one(y, expert):
        params, expert_id = expert
        w = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), -1)
        return y + w[:, None] * gated_mlp(params, x, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["experts"], jnp.asarray(held, jnp.int32)))
    return y, chosen


def expert_layer(p, x, s, quant, experts_held=None):
    """The routed part and, where ``s`` has it, the shared expert — which
    every share of the experts computes alike. Returns (y, chosen ids)."""
    y, chosen = routed_part(p, x, s, quant, experts_held)
    if s.shared:
        y = y + gated_mlp(p["shared"], x, quant)
    return y, chosen


def layer_forward(layer, h, s, kind, quant=None, experts_held=None):
    """One pre-norm block over windows h (B, T, hidden). Returns (h, chosen
    expert ids (B, T, k), or None for a dense layer)."""
    quant = quant or (lambda a: a)
    B, T, _ = h.shape
    u = rms_norm(h, layer["operator_norm"], s.eps)
    h = h + jnp.stack([attention(layer["attn"], u[b], s, kind, quant)
                       for b in range(B)])
    x = rms_norm(h, layer["ffn_norm"], s.eps).reshape(B * T, -1)
    if "mlp" in layer:
        return h + gated_mlp(layer["mlp"], x, quant).reshape(B, T, -1), None
    m, chosen = expert_layer(layer["moe"], x, s, quant, experts_held)
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
    from ``key`` one part at a time and dropped after use. Returns host
    ``(pooled, logprobs, chosen)``, ``chosen`` a list over the expert layers
    of (B, T, k) expert ids."""
    h = jax.jit(lambda k, t: init_embed(k, s)["embed"][t])(key, tokens)
    # init and pass in one program: a layer's float32 weights live only
    # inside it; one compile for each kind of layer
    step = jax.jit(lambda k, h, i, kind, dense: layer_forward(
        init_layer(k, s, i, kind, dense), h, s, kind, quant),
        static_argnums=(3, 4))
    chosen = []
    for index in range(s.layers):
        h, ids = step(key, h, index, s.layer_types[index],
                      index < s.dense_layers)
        if ids is not None:
            chosen.append(jax.device_get(ids))
    pooled, logprobs = jax.jit(
        lambda k, h, t: head_forward(init_head(k, s), h, t, s, quant))(
            key, h, tokens)
    return jax.device_get(pooled), jax.device_get(logprobs), chosen
