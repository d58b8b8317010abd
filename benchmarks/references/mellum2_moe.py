"""Plain reference: the Mellum 2 decoder (``config.json`` of
``JetBrains/Mellum2-12B-A2.5B-Instruct``, ``model_type`` ``mellum``) as a
prefill-only scorer — a pre-norm stack of grouped-query attention and
sparse-expert layers, three sliding-window attention layers to every full
one, each kind with its own rotary, a soft-max router, a final norm and an
untied head. Plain ``jax.numpy`` in float32; no kernel, no cache, no
batching, nothing of ``sparkdl_tpu``. Matrix precision is the caller's
(``jax.default_matmul_precision("highest")``).

The equations (``h`` a token's hidden state, ``u`` its normed copy; every key
is the config's):

  RMSNorm      x · rsqrt(mean(x²) + rms_norm_eps) · g
  block        h ← h + W_o attn_i(RMSNorm_1(h));  h ← h + moe(RMSNorm_2(h));
               attn_i by layer_types[i]; mlp_layer_types is ``sparse`` in
               every layer: no dense one
  attention    q = W_q u → 32 heads × 128;  k = W_k u, v = W_v u → 4 × 128
               (no bias); rotary on q and k, a head's two halves paired, with
               inverse frequencies ω and an amplitude a by the layer's kind
               (rope_parameters):
                 sliding_attention  ω_j = θ^(−2j/d), a = 1 (rope_type default)
                 full_attention     YaRN: ω_j = θ^(−2j/d) · ((1 − r_j) + r_j / factor),
                                    r_j = clip((j − low) / (high − low), 0, 1),
                                    low = ⌊dim(beta_fast)⌋, high = ⌈dim(beta_slow)⌉,
                                    dim(n) = d · ln(original_max_position_embeddings
                                    / (2π n)) / (2 ln θ), both clipped to [0, d − 1];
                                    a = attention_factor on cos and sin
               scores q · k / √d, soft-max over keys j ≤ t and, in a sliding
               layer, j > t − sliding_window (that many keys, the token's own
               among them); query head a reads key head a // (heads / kv_heads)
  expert layer p = softmax(W_r x) over all experts; the num_experts_per_tok
               largest; w = p[chosen] / Σ p[chosen] (norm_topk_prob);
               y = Σ_{e chosen, e held} w_e · W2_e (silu(W1_e x) ⊙ W3_e x);
               no shared expert, no bias, no scaling factor
  head         RMSNorm after the last block, then the rows of an untied head

Departures from the published description, each of which changes no result:
attention is a masked dense soft-max (the causal triangle and the band as a
mask over all keys), a key head's block of query heads and a block of queries
at a time, so that 16,384 positions fit; keys and values are repeated to the
query heads by broadcasting; the expert layer is given ``experts_held`` — it
routes over all experts, computes every held expert for every token and
masks by the routing (with every expert held, as in the benchmark's
configuration, it is the uncut layer); the head's logits are made a block of
positions at a time; there is no cache and no generation. Not in the
``config.json`` and so ``assumed`` (the configuration's file says so too): no
norm per head on queries and keys (no key names one); rope by halves; no
multi-token-prediction module (no key for one, and a scorer runs none).

Weights are made from the seed part by part (``init_embed``, ``init_layer``,
``init_head``), so that a caller never holds more than one layer in float32,
and every drawn value is rounded to bfloat16, the precision the model is
published in: the float32 passes here run on the very numbers the program
holds in bfloat16. An expert's weights depend on the key and the expert's id
alone, whichever share holds it; a layer's weights do not depend on its kind.
``quant`` is the control's hook: applied to both operands of every matrix
product that the configuration runs in bfloat16 (the router and the rotary
tables stay float32, as the configuration states). ``without(s, …)`` makes
the two faults this model can have: the span left out of the sliding layers,
and the plain rotary in the full ones.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp


def sizes(config):
    """The sizes the passes need, from the configuration's file: every width
    as published; ``layers`` / ``layer_types`` are what this chip holds,
    ``experts`` the published router width."""
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("every layer's mlp is sparse in this family")
    return SimpleNamespace(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_width=config["moe_intermediate_size"],
        experts=config["num_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"], eps=config["rms_norm_eps"],
        vocab=config["vocab_size"], layers=config["num_hidden_layers"],
        dense_layers=0, layer_types=tuple(config["layer_types"]),
        span=config["sliding_window"],
        rope={kind: dict(p) for kind, p in config["rope_parameters"].items()})


def without(s, what):
    """``s`` with one of the model's mechanisms taken out: ``"span"`` — the
    sliding layers read every earlier key; ``"yarn"`` — the full layers turn
    by the sliding layers' plain rotary, amplitude 1."""
    if what == "span":
        return SimpleNamespace(**{**vars(s), "span": None})
    if what == "yarn":
        return SimpleNamespace(**{**vars(s), "rope": {
            kind: s.rope["sliding_attention"] for kind in s.rope}})
    raise ValueError(f"no mechanism {what!r}")


# -- weights (the `assumed` distributions of the configuration's file) -------

# LFM2's recipe (references/lfm2_moe.py, PERF.md §6, PR 37), with what an
# untied head and attention without per-head norms change: the embedding's
# rows are the stream's body themselves, N(0, 1) (there the tied head kept
# them small and the first mixer made the body); every sub-layer adds an RMS
# near 0.15 or under to it — attention's W_o at 0.3 (a soft-max's mean is
# smaller than its values), an expert's W_2 at 0.25; and W_q and W_k carry
# the factor that the per-head gains carried there, so that scores have a
# deviation near 4 (6.5 in a full layer, by YaRN's amplitude twice) and
# attention is not a plain mean.
OUT_SCALE = {"attn": 0.3, "experts": 0.25}
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


def _mlp(key, hidden, width, out_scale):
    k = _keys(key, ("gate", "up", "down"))
    return {"gate": _normal(k["gate"], (hidden, width), hidden),
            "up": _normal(k["up"], (hidden, width), hidden),
            "down": _normal(k["down"], (width, hidden), width, out_scale)}


def init_embed(key, s):
    return {"embed": _normal(jax.random.fold_in(key, 1000),
                             (s.vocab, s.hidden), 1)}


def init_head(key, s):
    k = _keys(jax.random.fold_in(key, 2000), ("final_norm", "head"))
    return {"final_norm": _gain(k["final_norm"], s.hidden, 0.7, 1.3),
            "head": _normal(k["head"], (s.vocab, s.hidden), s.hidden)}


def init_layer(key, s, index, experts_held=None):
    """Layer ``index`` (it may be traced), its expert layer holding
    ``experts_held`` (default: the configuration's)."""
    held = s.experts_held if experts_held is None else tuple(experts_held)
    k = _keys(jax.random.fold_in(key, index), (
        "operator_norm", "ffn_norm", "q", "k", "v", "o", "router", "experts"))
    wide, narrow = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {
        "operator_norm": _gain(k["operator_norm"], s.hidden, 0.7, 1.3),
        "ffn_norm": _gain(k["ffn_norm"], s.hidden, 0.7, 1.3),
        "attn": {
            "q": _normal(k["q"], (s.hidden, wide), s.hidden, QK_SCALE),
            "k": _normal(k["k"], (s.hidden, narrow), s.hidden, QK_SCALE),
            "v": _normal(k["v"], (s.hidden, narrow), s.hidden),
            "out": _normal(k["o"], (wide, s.hidden), wide, OUT_SCALE["attn"])},
        "moe": {
            "router": _normal(k["router"], (s.hidden, s.experts), s.hidden),
            # leaves stacked over the held experts
            "experts": jax.vmap(lambda e: _mlp(
                jax.random.fold_in(k["experts"], e), s.hidden,
                s.expert_width, OUT_SCALE["experts"]))(
                    jnp.asarray(held, jnp.int32))}}


# -- the passes ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_table(p, head_dim):
    """(inverse frequencies (head_dim / 2,), amplitude) of one kind's entry
    of ``rope_parameters``."""
    half = head_dim // 2
    theta = float(p["rope_theta"])
    plain = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if p["rope_type"] == "default":
        return plain, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {p['rope_type']!r}")

    def dimension(turns):
        return head_dim * math.log(p["original_max_position_embeddings"] / (
            2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(dimension(p["beta_fast"])), 0)
    high = min(math.ceil(dimension(p["beta_slow"])), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * ((1.0 - ramp) + ramp / p["factor"]),
            float(p["attention_factor"]))


def rotary(x, frequencies, amplitude):
    """x (..., T, d): position t turns the pair (x[i], x[i + d/2]) by
    t · frequencies[i] — the two halves pair up (`assumed`) — and cos and sin
    carry the amplitude."""
    half = x.shape[-1] // 2
    t = jnp.arange(x.shape[-2], dtype=jnp.float32)
    angle = t[:, None] * frequencies
    cos, sin = amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mm(a, b, quant):
    return quant(a) @ quant(b)


def attention(p, x, s, kind, quant, block=512):
    """x (T, hidden), one window: a masked dense soft-max over all keys, one
    key head's query heads and ``block`` queries at a time."""
    T = x.shape[0]
    group = s.heads // s.kv_heads
    block = block if T % block == 0 else T
    span = s.span if kind == "sliding_attention" else None

    def heads(a):                           # (T, n · d) → (n, T, d)
        return jnp.swapaxes(a.reshape(T, -1, s.head_dim), 0, 1)

    table = rope_table(s.rope[kind], s.head_dim)
    q = rotary(heads(_mm(x, p["q"], quant)), *table).reshape(
        s.kv_heads, group, T // block, block, s.head_dim)
    k = rotary(heads(_mm(x, p["k"], quant)), *table)
    v = heads(_mm(x, p["v"], quant))
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

    out = jax.lax.map(one_key_head, (q, k, v)).reshape(s.heads, T, -1)
    return _mm(jnp.swapaxes(out, 0, 1).reshape(T, -1), p["out"], quant)


def gated_mlp(p, x, quant):
    return _mm(jax.nn.silu(_mm(x, p["gate"], quant)) * _mm(x, p["up"], quant),
               p["down"], quant)


def route(p, x, s):
    """(chosen expert ids (N, k), their weights (N, k)), float32."""
    top, chosen = jax.lax.top_k(jax.nn.softmax(x @ p["router"], -1), s.top_k)
    if s.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, top


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


expert_layer = routed_part          # no shared expert: the layer is its sum


def layer_forward(layer, h, s, kind, quant=None, experts_held=None):
    """One pre-norm block over windows h (B, T, hidden). Returns (h, chosen
    expert ids (B, T, k))."""
    quant = quant or (lambda a: a)
    B, T, _ = h.shape
    u = rms_norm(h, layer["operator_norm"], s.eps)
    h = h + jnp.stack([attention(layer["attn"], u[b], s, kind, quant)
                       for b in range(B)])
    x = rms_norm(h, layer["ffn_norm"], s.eps).reshape(B * T, -1)
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
    ``(pooled, logprobs, chosen)``, ``chosen`` a list over the layers of
    (B, T, k) expert ids."""
    h = jax.jit(lambda k, t: init_embed(k, s)["embed"][t])(key, tokens)
    # init and pass in one program: a layer's float32 weights live only
    # inside it; one compile for each kind of layer
    step = jax.jit(lambda k, h, i, kind: layer_forward(
        init_layer(k, s, i), h, s, kind, quant), static_argnums=3)
    chosen = []
    for index in range(s.layers):
        h, ids = step(key, h, index, s.layer_types[index])
        chosen.append(jax.device_get(ids))
    pooled, logprobs = jax.jit(
        lambda k, h, t: head_forward(init_head(k, s), h, t, s, quant))(
            key, h, tokens)
    return jax.device_get(pooled), jax.device_get(logprobs), chosen
