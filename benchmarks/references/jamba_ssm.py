"""Plain reference: the Jamba decoder without expert layers (``config.json``
and the ``jamba`` modelling code that ``ai21labs/AI21-Jamba2-3B`` names) as a
prefill-only scorer — a pre-norm stack of state-space (Mamba-1) mixers with an
attention layer every ``attn_layer_period`` layers, a gated MLP after every
mixer (``num_experts`` 1), a final norm and the head tied to the embedding;
no positional encoding of any kind. Plain ``jax.numpy`` in float32; no
kernel, no batching, nothing of ``sparkdl_tpu``. Matrix precision is the
caller's (``jax.default_matmul_precision("highest")``).

The equations (``h`` a token's hidden state, ``u`` its normed copy):

  RMSNorm      x · rsqrt(mean(x²) + eps) · g
  block        h ← h + mixer_i(RMSNorm_in(h));  h ← h + mlp_i(RMSNorm_ffn(h))
               mixer_i attention where i % attn_layer_period ==
               attn_layer_offset, else the state-space mixer
  state space  [x ; z] = W_in u (hidden → 2 · d_inner, split in that order,
               no bias);  x ← silu(conv(x)): c[t] = Σ_{j=0..L−1} k[:, j] ⊙
               x[t − (L−1) + j] + b_conv (depthwise, causal, L = mamba_d_conv
               taps, zeros before the window);
               [δ ; B ; C] = W_x x (d_inner → dt_rank + 2 · d_state);
               δ ← RMSNorm_dt(δ), B ← RMSNorm_b(B), C ← RMSNorm_c(C);
               Δ = softplus(W_dt δ + b_dt);  A = −exp(A_log) (d_inner × d_state)
               s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t,  s_0 = 0
               y_t = s_t C_t + D ⊙ x_t;  out = W_out (y ⊙ silu(z))
  attention    q = W_q u → heads × d;  k = W_k u, v = W_v u → kv_heads × d;
               no bias, no norm per head, no rotary; causal softmax of
               q·kᵀ / √d, query head a reads key head a // (heads / kv_heads)
  gated MLP    W_2 (silu(W_1 x) ⊙ W_3 x)
  head         RMSNorm_out after the last block, then the embedding's rows
               as the head

Departures from the published code, each of which changes no result: the
convolution is written as L shifted products and not as a padded ``Conv1d``
cut back to the window; the recurrence is a ``lax.scan`` over positions (all
sampled rows at once) where the published slow path loops in Python and its
fast path calls a CUDA kernel; the discretisation is the published one
(``exp(Δ A)`` for the state, ``Δ B`` for the input — no exact integral of B);
the one key head is read by every query head through broadcasting and not
through ``repeat_kv``; attention is a masked dense soft-max over blocks of
queries so that a window's scores fit; the head runs over blocks of
positions; there is no cache and no generation.

Weights are made from the seed part by part (``init_embed``, ``init_layer``,
``init_head``), so that a caller never holds more than one layer in float32,
and every drawn value is rounded to bfloat16, the precision the model is
published in: the float32 passes here run on the very numbers the program
holds in bfloat16. The head IS the embedding (``init_head`` makes the same
rows again). ``quant`` is the control's hook: applied to both operands of
every matrix product that the configuration runs in bfloat16 (the taps, the
inner norms, softplus, the recurrence and the gates, which are no matrix
products, stay float32, as the configuration states).

``without(sizes, name)`` gives the sizes of a reference with one thing of the
model left out, for the two faults a program of this model can have:
``"carry"`` — the state reset to zero every ``CARRY_RESET`` positions, what a
blocked scan computes when it drops the hand-over between its blocks — and
``"inner_norms"`` — δ, B and C used as ``W_x`` leaves them.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp

CARRY_RESET = 256       # positions between two resets of the ``carry`` fault


def sizes(config):
    """The sizes the passes need, from the configuration's file, every one
    as published."""
    return SimpleNamespace(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_inner=config["mamba_expand"] * config["hidden_size"],
        d_state=config["mamba_d_state"], dt_rank=config["mamba_dt_rank"],
        taps=config["mamba_d_conv"], dense_width=config["intermediate_size"],
        eps=config["rms_norm_eps"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"],
        attn_period=config["attn_layer_period"],
        attn_offset=config["attn_layer_offset"],
        carry_reset=0, inner_norms=True)


def without(s, name):
    """``s`` with one thing of the model left out (see the docstring)."""
    changed = {"carry": {"carry_reset": CARRY_RESET},
               "inner_norms": {"inner_norms": False}}
    if name not in changed:
        raise ValueError(f"nothing named {name!r} to leave out; there are "
                         f"{sorted(changed)}")
    return SimpleNamespace(**{**vars(s), **changed[name]})


def is_attention(s, index):
    return index % s.attn_period == s.attn_offset


# -- weights (the `assumed` distributions of the configuration's file) -------

# The projections that write to the residual stream, against 1/√fan_in, as
# lfm2_moe.py's and for its reason: the first layer's mixer at full strength
# makes the stream's body (the embedding's rows are small, for the tied
# head's sake), every later sub-layer adds an RMS near 0.15–0.3 to it, so
# that the stream stays O(1) over 28 layers and rounding in bfloat16 does not
# read like rounding in float8.
OUT_SCALE = {"ssm": 0.15, "attn": 0.3, "mlp": 0.25}
DT_RANGE = (1e-3, 1e-1)     # Mamba's: Δ's bias is its inverse softplus


def _published(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, fan_in, scale=1.0):
    return _published(jax.random.normal(key, shape, jnp.float32) * (
        scale * fan_in ** -0.5))


def _gain(key, n, lo=0.7, hi=1.3):
    return _published(jax.random.uniform(key, (n,), jnp.float32, lo, hi))


def _keys(key, names):
    return {name: jax.random.fold_in(key, i) for i, name in enumerate(names)}


def init_embed(key, s):
    return {"embed": _normal(jax.random.fold_in(key, 1000),
                             (s.vocab, s.hidden), s.hidden)}


def init_head(key, s):
    return {"final_norm": _gain(jax.random.fold_in(key, 2000), s.hidden),
            "head": init_embed(key, s)["embed"]}            # tied


def init_layer(key, s, index, attention=None):
    """Layer ``index``; ``attention`` says whether its mixer is attention
    (default: by the configuration's period and offset, which needs ``index``
    to be a number and not traced)."""
    if attention is None:
        attention = is_attention(s, index)
    k = _keys(jax.random.fold_in(key, index), (
        "operator_norm", "ffn_norm", "in", "taps", "conv_bias", "x",
        "dt_norm", "b_norm", "c_norm", "dt", "dt_bias", "out", "q", "k", "v",
        "o", "gate", "up", "down"))
    layer = {"operator_norm": _gain(k["operator_norm"], s.hidden),
             "ffn_norm": _gain(k["ffn_norm"], s.hidden)}
    # (index may be traced: the first layer's scale is chosen by arithmetic)
    first = jnp.asarray(index == 0, jnp.float32)

    def out_scale(kind):
        return first + (1 - first) * OUT_SCALE[kind]

    if attention:
        wide, narrow = s.heads * s.head_dim, s.kv_heads * s.head_dim
        # W_q and W_k carry a factor 2 (mellum2_moe.py's rule for attention
        # without per-head gains): scores with a deviation near 4, so that
        # attention is not a plain mean
        layer["attn"] = {
            "q": _normal(k["q"], (s.hidden, wide), s.hidden, 2.0),
            "k": _normal(k["k"], (s.hidden, narrow), s.hidden, 2.0),
            "v": _normal(k["v"], (s.hidden, narrow), s.hidden),
            "out": _normal(k["o"], (wide, s.hidden), wide,
                           out_scale("attn"))}
    else:
        inner, states, rank = s.d_inner, s.d_state, s.dt_rank
        low, high = (jnp.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(k["dt_bias"], (inner,), jnp.float32,
                                        low, high))
        layer["ssm"] = {
            "in": _normal(k["in"], (s.hidden, 2 * inner), s.hidden),
            "taps": _normal(k["taps"], (inner, s.taps), s.taps),
            "conv_bias": _published(0.1 * jax.random.normal(
                k["conv_bias"], (inner,), jnp.float32)),
            "x": _normal(k["x"], (inner, rank + 2 * states), inner),
            "dt_norm": _gain(k["dt_norm"], rank),
            "b_norm": _gain(k["b_norm"], states),
            "c_norm": _gain(k["c_norm"], states),
            "dt": _published(jax.random.uniform(
                k["dt"], (rank, inner), jnp.float32, -1.0, 1.0)
                * rank ** -0.5),
            # softplus(dt_bias) = dt: log-uniform over DT_RANGE
            "dt_bias": _published(dt + jnp.log(-jnp.expm1(-dt))),
            "a_log": _published(jnp.broadcast_to(jnp.log(jnp.arange(
                1, states + 1, dtype=jnp.float32)), (inner, states))),
            "d": jnp.ones((inner,), jnp.float32),
            "out": _normal(k["out"], (inner, s.hidden), inner,
                           out_scale("ssm"))}
    layer["mlp"] = {
        "gate": _normal(k["gate"], (s.hidden, s.dense_width), s.hidden),
        "up": _normal(k["up"], (s.hidden, s.dense_width), s.hidden),
        "down": _normal(k["down"], (s.dense_width, s.hidden), s.dense_width,
                        OUT_SCALE["mlp"])}
    return layer


# -- the passes ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(a, b, quant):
    return quant(a) @ quant(b)


def conv_taps(x, taps, bias):
    """c[t] = Σ_j taps[:, j] ⊙ x[t − (L−1) + j] + bias over windows x
    (B, T, channels), zeros before the window."""
    T, L = x.shape[1], taps.shape[1]
    padded = jnp.pad(x, ((0, 0), (L - 1, 0), (0, 0)))
    return sum(taps[:, j] * padded[:, j:j + T] for j in range(L)) + bias


def recurrence(x, delta, a, b, c, reset=0):
    """y_t = s_t C_t with s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t and
    s_0 = 0, over windows x, Δ (B, T, d_inner) and B, C (B, T, d_state); A
    (d_inner, d_state): one position of every window a step. With a
    ``reset`` the state is zeroed before every position that is a multiple
    of it (the ``carry`` fault)."""
    def step(s, at):
        x, delta, b, c, t = at
        if reset:
            s = jnp.where(t % reset == 0, 0.0, s)
        s = jnp.exp(delta[..., None] * a) * s \
            + (delta * x)[..., None] * b[:, None, :]
        return s, jnp.sum(s * c[:, None, :], -1)

    T = x.shape[1]
    first = jnp.zeros(x.shape[:1] + a.shape, jnp.float32)
    _, y = jax.lax.scan(step, first, (*(jnp.swapaxes(v, 0, 1) for v in (
        x, delta, b, c)), jnp.arange(T)))
    return jnp.swapaxes(y, 0, 1)


def state_space(p, u, s, quant):
    """u (B, T, hidden), every window at once."""
    x, z = jnp.split(_mm(u, p["in"], quant), 2, -1)
    x = jax.nn.silu(conv_taps(x, p["taps"], p["conv_bias"]))
    dt, b, c = jnp.split(_mm(x, p["x"], quant),
                         (s.dt_rank, s.dt_rank + s.d_state), -1)
    if s.inner_norms:
        dt, b, c = (rms_norm(v, p[name], s.eps) for v, name in (
            (dt, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    delta = jax.nn.softplus(_mm(dt, p["dt"], quant) + p["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(p["a_log"]), b, c, s.carry_reset)
    return _mm((y + p["d"] * x) * jax.nn.silu(z), p["out"], quant)


def attention(p, x, s, quant, block=512):
    """x (T, hidden), one window: a masked dense soft-max over all keys,
    ``block`` queries of every head at a time."""
    T = x.shape[0]
    block = block if T % block == 0 else T
    group = s.heads // s.kv_heads

    def heads(a):                           # (T, n · d) → (n, T, d)
        return jnp.swapaxes(a.reshape(T, -1, s.head_dim), 0, 1)

    q = heads(_mm(x, p["q"], quant)).reshape(
        s.kv_heads, group, T // block, block, s.head_dim)
    k, v = heads(_mm(x, p["k"], quant)), heads(_mm(x, p["v"], quant))
    keys = jnp.arange(T)[None, :]

    def one_block(args):
        q, first = args                     # (kv_heads, group, block, d)
        rows = first + jnp.arange(block)[:, None]
        scores = jnp.einsum("grqd,gkd->grqk", quant(q), quant(k)) \
            * s.head_dim ** -0.5
        weights = jax.nn.softmax(jnp.where(keys <= rows, scores, -jnp.inf),
                                 -1)
        return jnp.einsum("grqk,gkd->grqd", quant(weights), quant(v))

    out = jax.lax.map(one_block, (jnp.moveaxis(q, 2, 0),
                                  jnp.arange(0, T, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(s.heads, T, -1)
    return _mm(jnp.swapaxes(out, 0, 1).reshape(T, -1), p["out"], quant)


def gated_mlp(p, x, quant):
    return _mm(jax.nn.silu(_mm(x, p["gate"], quant)) * _mm(x, p["up"], quant),
               p["down"], quant)


def layer_forward(layer, h, s, quant=None):
    """One pre-norm block over windows h (B, T, hidden)."""
    quant = quant or (lambda a: a)
    u = rms_norm(h, layer["operator_norm"], s.eps)
    if "ssm" in layer:
        h = h + state_space(layer["ssm"], u, s, quant)
    else:
        h = h + jnp.stack([attention(layer["attn"], row, s, quant)
                           for row in u])
    x = rms_norm(h, layer["ffn_norm"], s.eps)
    return h + jnp.stack([gated_mlp(layer["mlp"], row, quant) for row in x])


def head_forward(head, h, tokens, s, quant=None, block=2048):
    """(pooled (B, hidden): the mean over positions of the final-norm state;
    logprobs (B, T): log p(x[t+1] | x[≤t]) over the vocabulary, the last 0),
    ``block`` positions at a time."""
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


def forward(key, s, tokens, quant=None, stream=None):
    """The whole pass over windows ``tokens`` (B, T) int32, the weights made
    from ``key`` one part at a time and dropped after use. Returns host
    ``(pooled, logprobs)``. ``stream``, a list, gains the root mean square of
    the residual stream after the embedding and after every layer."""
    def rms(h):
        return float(jnp.sqrt(jnp.mean(h * h)))

    h = jax.jit(lambda k, t: init_embed(k, s)["embed"][t])(key, tokens)
    # init and pass in one program: a layer's float32 weights live only
    # inside it; one compile for each kind of layer
    step = jax.jit(lambda k, h, i, attention: layer_forward(
        init_layer(k, s, i, attention), h, s, quant), static_argnums=3)
    for index in range(s.layers):
        if stream is not None:
            stream.append(rms(h))
        h = step(key, h, index, is_attention(s, index))
    if stream is not None:
        stream.append(rms(h))
    pooled, logprobs = jax.jit(
        lambda k, h, t: head_forward(init_head(k, s), h, t, s, quant))(
            key, h, tokens)
    return jax.device_get(pooled), jax.device_get(logprobs)
