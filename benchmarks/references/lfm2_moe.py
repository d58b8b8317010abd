"""Plain reference: the LFM2-MoE decoder (``config.json`` and the ``lfm2_moe``
modelling code of ``LiquidAI/LFM2-8B-A1B``) as a prefill-only scorer — a
pre-norm stack whose mixers are gated short convolutions with a few
grouped-query attention layers among them, a gated MLP in the leading dense
layers and a sigmoid-routed sparse-expert layer with a selection bias in the
others, a final norm and the head tied to the embedding. Plain ``jax.numpy``
in float32; no kernel, no batching, nothing of ``sparkdl_tpu``. Matrix
precision is the caller's (``jax.default_matmul_precision("highest")``).

The equations (``h`` a token's hidden state, ``u`` its normed copy):

  RMSNorm      x · rsqrt(mean(x²) + eps) · g
  block        h ← h + mixer_i(RMSNorm_op(h));  h ← h + ffn_i(RMSNorm_ffn(h))
               mixer_i by layer_types[i]; ffn_i a gated MLP for
               i < num_dense_layers, else the expert layer
  conv         [B ; C ; x̃] = W_in u (hidden → 3 · hidden, split in that
               order);  z = B ⊙ x̃;  c[t] = Σ_{j=0..L−1} k[:, j] ⊙ z[t − (L−1) + j]
               (depthwise, causal, L = conv_L_cache taps, zeros before the
               window, no bias, no activation);  y = W_out (C ⊙ c)
  attention    q = W_q u → heads × d;  k = W_k u, v = W_v u → kv_heads × d;
               q ← RMSNorm_q(q), k ← RMSNorm_k(k) per head over its d (one
               gain of d each, shared by the heads); rotary on q and k (a
               head's two halves pair up); causal softmax of q·kᵀ / √d,
               query head a reads key head a // (heads / kv_heads); W_o
  gated MLP    W_2 (silu(W_1 x) ⊙ W_3 x)
  expert layer s = sigmoid(W_r x) over all experts; chosen = top-k of s + b
               (b the selection bias); w = s[chosen] / (Σ s[chosen] + 1e-6),
               times the scaling factor;
               y = Σ_{e chosen, e held} w_e · expert_e(x); no shared expert
  head         RMSNorm_out after the last block (``embedding_norm``), then
               the embedding's rows as the head

Departures from the published code, each of which changes no result: the
convolution is written as L shifted products and not as a padded ``Conv1d``
cut back to the window; keys and values are repeated to the query heads by
``jnp.repeat`` (the published ``repeat_kv``); the expert layer is given
``experts_held`` — it routes over all experts, computes every held expert
for every token and masks by the routing, and what absent experts would add
is left out (with every expert held, as in the benchmark's configuration, it
is the uncut layer); there is no cache and no generation.

Weights are made from the seed part by part (``init_embed``, ``init_layer``,
``init_head``), so that a caller never holds more than one layer in float32,
and every drawn value is rounded to bfloat16, the precision the model is
published in: the float32 passes here run on the very numbers the program
holds in bfloat16. An expert's weights depend on the key and the expert's id
alone, whichever share holds it. The head IS the embedding (``init_head`` makes the same
rows again). ``quant`` is the control's hook: applied to both operands of
every matrix product that the configuration runs in bfloat16 (the router,
and the gates and taps of the convolution, which are no matrix products,
stay float32, as the configuration states).
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp


def sizes(config):
    """The sizes the passes need, from the configuration's file: every width
    as published; ``layers`` / ``dense_layers`` / ``layer_types`` are what
    this chip holds, ``experts`` the published router width."""
    return SimpleNamespace(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        taps=config["conv_L_cache"], dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        experts=config["num_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"],
        scaling=config["routed_scaling_factor"],
        expert_bias=config["use_expert_bias"], eps=config["norm_eps"],
        theta=config["rope_theta"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        dense_layers=config["num_dense_layers"],
        layer_types=tuple(config["layer_types"]))


# -- weights (the `assumed` distributions of the configuration's file) -------

# The projections that write to the residual stream, against 1/√fan_in: the
# first layer's mixer at full strength makes the stream's body (RMS near 1;
# the embedding's rows are small, for the tied head's sake); every later
# mixer and the dense MLP add an RMS near 0.15 to it, an expert layer half
# that. A stack whose sub-layers are as strong as the stream is chaotic at
# this depth — rounding in bfloat16 then reads like rounding in float8 — and
# a token whose fourth and fifth router scores lie within rounding moves by a
# whole expert, which the next layers' taps spread to its neighbours (PERF.md
# §6, PR 37: the readings that chose these factors).
OUT_SCALE = {"conv": 0.15, "attn": 0.3, "mlp": 0.25, "experts": 0.25}
BIAS_STD = 0.05         # the selection bias


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
                             (s.vocab, s.hidden), s.hidden)}


def init_head(key, s):
    return {"final_norm": _gain(jax.random.fold_in(key, 2000), s.hidden,
                                0.7, 1.3),
            "head": init_embed(key, s)["embed"]}            # tied


def init_layer(key, s, index, dense, experts_held=None, kind=None):
    """Layer ``index``: its mixer by ``kind`` (default: the configuration's
    ``layer_types[index]``, which needs ``index`` to be a number and not
    traced), a gated MLP if ``dense``, else an expert layer holding
    ``experts_held`` (default: the configuration's)."""
    held = s.experts_held if experts_held is None else tuple(experts_held)
    kind = s.layer_types[index] if kind is None else kind
    k = _keys(jax.random.fold_in(key, index), (
        "operator_norm", "ffn_norm", "in", "taps", "out", "q", "k", "v", "o",
        "q_norm", "k_norm", "mlp", "router", "expert_bias", "experts"))
    layer = {"operator_norm": _gain(k["operator_norm"], s.hidden, 0.7, 1.3),
             "ffn_norm": _gain(k["ffn_norm"], s.hidden, 0.7, 1.3)}
    # (index may be traced: the first layer's scale is chosen by arithmetic)
    first = jnp.asarray(index == 0, jnp.float32)
    if kind == "conv":
        layer["conv"] = {
            "in": _normal(k["in"], (s.hidden, 3 * s.hidden), s.hidden),
            "taps": _normal(k["taps"], (s.hidden, s.taps), s.taps),
            "out": _normal(k["out"], (s.hidden, s.hidden), s.hidden,
                           first + (1 - first) * OUT_SCALE["conv"])}
    elif kind == "full_attention":
        wide, narrow = s.heads * s.head_dim, s.kv_heads * s.head_dim
        layer["attn"] = {
            "q": _normal(k["q"], (s.hidden, wide), s.hidden),
            "k": _normal(k["k"], (s.hidden, narrow), s.hidden),
            "v": _normal(k["v"], (s.hidden, narrow), s.hidden),
            "q_norm": _gain(k["q_norm"], s.head_dim, 1.4, 2.6),
            "k_norm": _gain(k["k_norm"], s.head_dim, 1.4, 2.6),
            "out": _normal(k["o"], (wide, s.hidden), wide,
                           first + (1 - first) * OUT_SCALE["attn"])}
    else:
        raise ValueError(f"no layer type {kind!r}")
    if dense:
        layer["mlp"] = _mlp(k["mlp"], s.hidden, s.dense_width,
                            OUT_SCALE["mlp"])
        return layer
    layer["moe"] = {
        "router": _normal(k["router"], (s.hidden, s.experts), s.hidden),
        # leaves stacked over the held experts
        "experts": jax.vmap(lambda e: _mlp(
            jax.random.fold_in(k["experts"], e), s.hidden, s.expert_width,
            OUT_SCALE["experts"]))(
                jnp.asarray(held, jnp.int32))}
    if s.expert_bias:
        layer["moe"]["expert_bias"] = _published(BIAS_STD * jax.random.normal(
            k["expert_bias"], (s.experts,), jnp.float32))
    return layer


# -- the passes ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rotary(x, theta):
    """x (..., T, d): position t turns the pair (x[i], x[i + d/2]) by
    t · theta^(−2i/d) — the two halves pair up (`assumed`)."""
    half = x.shape[-1] // 2
    t = jnp.arange(x.shape[-2], dtype=jnp.float32)
    angle = t[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32)
                                   / half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mm(a, b, quant):
    return quant(a) @ quant(b)


def conv_taps(z, taps):
    """c[t] = Σ_j taps[:, j] ⊙ z[t − (L−1) + j] over one window z (T, hidden),
    zeros before the window."""
    T, L = z.shape[0], taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((L - 1, z.shape[1]), z.dtype), z])
    return sum(taps[:, j] * padded[j:j + T] for j in range(L))


def short_conv(p, x, quant):
    """x (T, hidden), one window."""
    gate_in, gate_out, carried = jnp.split(_mm(x, p["in"], quant), 3, -1)
    return _mm(gate_out * conv_taps(gate_in * carried, p["taps"]), p["out"],
               quant)


def attention(p, x, s, quant, block=512):
    """x (T, hidden), one window: masked dense softmax over blocks of
    queries, keys and values repeated to the query heads."""
    T = x.shape[0]

    def heads(a):                           # (T, n · d) → (n, T, d)
        return jnp.swapaxes(a.reshape(T, -1, s.head_dim), 0, 1)

    q = rotary(rms_norm(heads(_mm(x, p["q"], quant)), p["q_norm"], s.eps),
               s.theta)
    k = rotary(rms_norm(heads(_mm(x, p["k"], quant)), p["k_norm"], s.eps),
               s.theta)
    v = heads(_mm(x, p["v"], quant))
    k, v = (jnp.repeat(a, s.heads // s.kv_heads, 0) for a in (k, v))
    out = []
    for lo in range(0, T, block):
        rows = jnp.arange(lo, min(lo + block, T))
        scores = jnp.einsum("hqd,hkd->hqk", quant(q[:, lo:lo + block]),
                            quant(k)) * s.head_dim ** -0.5
        scores = jnp.where(jnp.arange(T)[None, :] <= rows[:, None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("hqk,hkd->hqd",
                              quant(jax.nn.softmax(scores, -1)), quant(v)))
    out = jnp.swapaxes(jnp.concatenate(out, 1), 0, 1).reshape(T, -1)
    return _mm(out, p["out"], quant)


def gated_mlp(p, x, quant):
    return _mm(jax.nn.silu(_mm(x, p["gate"], quant)) * _mm(x, p["up"], quant),
               p["down"], quant)


def route(p, x, s):
    """(chosen expert ids (N, k), their weights (N, k)), float32: the bias
    enters the choice and not the weights."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["expert_bias"] if s.expert_bias
                              else scores, s.top_k)
    top = jnp.take_along_axis(scores, chosen, -1)
    if s.norm_topk:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-6)
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


expert_layer = routed_part          # no shared expert: the layer is its sum


def layer_forward(layer, h, s, quant=None, experts_held=None):
    """One pre-norm block over windows h (B, T, hidden). Returns (h, chosen
    expert ids (B, T, k) of an expert layer, else None)."""
    quant = quant or (lambda a: a)
    B, T, _ = h.shape
    u = rms_norm(h, layer["operator_norm"], s.eps)
    if "conv" in layer:
        mixed = [short_conv(layer["conv"], u[b], quant) for b in range(B)]
    else:
        mixed = [attention(layer["attn"], u[b], s, quant) for b in range(B)]
    h = h + jnp.stack(mixed)
    x = rms_norm(h, layer["ffn_norm"], s.eps).reshape(B * T, -1)
    chosen = None
    if "moe" in layer:
        m, chosen = expert_layer(layer["moe"], x, s, quant, experts_held)
        chosen = chosen.reshape(B, T, -1)
    else:
        m = gated_mlp(layer["mlp"], x, quant)
    return h + m.reshape(B, T, -1), chosen


def head_forward(head, h, tokens, s, quant=None):
    """(pooled (B, hidden): the mean over positions of the final-norm state;
    logprobs (B, T): log p(x[t+1] | x[≤t]) over the vocabulary, the last 0),
    a window at a time."""
    quant = quant or (lambda a: a)
    x = rms_norm(h, head["final_norm"], s.eps)

    def row(args):
        x, ids = args
        logp = jax.nn.log_softmax(_mm(x, head["head"].T, quant), -1)
        return jnp.pad(jnp.take_along_axis(logp[:-1], ids[1:, None],
                                           -1)[:, 0], (0, 1))

    return jnp.mean(x, 1), jax.lax.map(row, (x, tokens))


def forward(key, s, tokens, quant=None):
    """The whole pass over windows ``tokens`` (B, T) int32, the weights made
    from ``key`` one part at a time and dropped after use. Returns host
    ``(pooled, logprobs, chosen)``, ``chosen`` a list over the expert layers
    of (B, T, k) expert ids."""
    h = jax.jit(lambda k, t: init_embed(k, s)["embed"][t])(key, tokens)
    # init and pass in one program: a layer's float32 weights live only
    # inside it; one compile for each kind of layer the stack has
    step = jax.jit(lambda k, h, i, dense, kind: layer_forward(
        init_layer(k, s, i, dense, kind=kind), h, s, quant),
        static_argnums=(3, 4))
    chosen = []
    for index in range(s.layers):
        h, ids = step(key, h, index, index < s.dense_layers,
                      s.layer_types[index])
        if ids is not None:
            chosen.append(jax.device_get(ids))
    pooled, logprobs = jax.jit(
        lambda k, h, t: head_forward(init_head(k, s), h, t, s, quant))(
            key, h, tokens)
    return (jax.device_get(pooled), jax.device_get(logprobs),
            chosen)
