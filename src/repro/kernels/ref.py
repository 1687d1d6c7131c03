"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels/ops are tested
against (``tests/test_kernels.py`` sweeps shapes/dtypes and asserts
allclose).  No Pallas, no tiling — straight dense math.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# conv2d (+ fused ReLU) — the paper's streaming conv oracle
# ---------------------------------------------------------------------------


def conv2d(
    x: jax.Array,          # (B, H, W, C_in)
    w: jax.Array,          # (KH, KW, C_in, C_out)
    *,
    stride: int = 1,
    padding: str | tuple = "SAME",
    fuse_relu: bool = False,
    epilogue: str | None = None,
) -> jax.Array:
    """NHWC conv; int8 inputs accumulate in int32 (paper's PTQ regime).
    ``padding`` is ``"SAME"`` / ``"VALID"`` or an explicit
    ``((top, bottom), (left, right))`` pair sequence (passed straight to
    ``lax.conv_general_dilated`` — the asymmetric-pads import path).
    ``epilogue`` mirrors the kernel's fused tails (relu / relu6 /
    squared_relu)."""
    if fuse_relu and epilogue not in (None, "relu"):
        raise ValueError(f"fuse_relu=True conflicts with epilogue={epilogue!r}")
    if jnp.issubdtype(x.dtype, jnp.integer):
        acc_dtype = jnp.int32
    else:
        acc_dtype = jnp.float32
    out = lax.conv_general_dilated(
        x.astype(acc_dtype),
        w.astype(acc_dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return _conv_epilogue(out, "relu" if fuse_relu else epilogue)


def _conv_epilogue(out: jax.Array, epilogue: str | None) -> jax.Array:
    if epilogue is None:
        return out
    if epilogue not in ("relu", "relu6", "squared_relu"):
        raise ValueError(f"unsupported conv epilogue {epilogue!r}")
    return unary(epilogue, out)


def depthwise_conv2d(
    x: jax.Array,          # (B, H, W, C)
    w: jax.Array,          # (KH, KW, C)
    *,
    stride: int = 1,
    padding: str | tuple = "SAME",
    bias: jax.Array | None = None,
    epilogue: str | None = None,
) -> jax.Array:
    """NHWC depthwise conv: channel ``c`` convolved with ``w[..., c]``
    alone (``feature_group_count = C``); integers accumulate in int32.
    ``bias`` (one value per channel) and ``epilogue`` follow the
    kernel's order: bias first, then the activation."""
    acc_dtype = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) \
        else jnp.float32
    c = x.shape[-1]
    out = lax.conv_general_dilated(
        x.astype(acc_dtype),
        w.astype(acc_dtype).reshape(w.shape[:2] + (1, c)),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )
    if bias is not None:
        out = out + bias.astype(acc_dtype)
    return _conv_epilogue(out, epilogue)


# ---------------------------------------------------------------------------
# DFG payload / epilogue primitives — shared by the DFG interpreter
# (repro.passes.interp) and the per-group Pallas lowering
# (repro.kernels.ops.lower_group), so both execute identical semantics.
# Kinds are the *string values* of repro.core.ir.PayloadKind (a str enum,
# so the enum members themselves compare equal and pass straight through).
# ---------------------------------------------------------------------------


def unary(kind: str, x: jax.Array) -> jax.Array:
    if kind == "relu":
        return jnp.maximum(x, 0)
    if kind == "relu6":
        return jnp.clip(x, 0, 6)
    if kind == "squared_relu":
        r = jnp.maximum(x, 0)
        return r * r
    if kind == "identity":
        return x
    if kind == "exp":
        return jnp.exp(x.astype(jnp.float32))
    raise NotImplementedError(f"unary payload {kind}")


def binary(kind: str, a: jax.Array, b: jax.Array) -> jax.Array:
    if kind == "add":
        return a + b
    if kind == "mul":
        return a * b
    if kind == "max":
        return jnp.maximum(a, b)
    raise NotImplementedError(f"binary payload {kind}")


def _div_exact(x: jax.Array, n: int) -> jax.Array:
    """The DIV exit path shared by every avg-pool realization: floor
    division for integer accumulators (the int8 PTQ regime — identical
    semantics in the interpreter, the Pallas lowering, and the modeled
    HLS datapath), true division for floats."""
    if n == 1:
        return x
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x // n
    return x / n


def pool_reduce(kind: str, x: jax.Array, window: tuple[int, ...]) -> jax.Array:
    """Non-overlapping window reduction: axis ``i`` shrinks by
    ``window[i]`` and ``kind`` combines each tile (a fused pool
    epilogue's semantics — max pool for kind="max").

    ``kind="avg"`` accumulates with ADD and takes the DIV exit path
    *once*, over the whole window product — not per axis — so integer
    floor division matches the single divider on the stream-exit
    datapath."""
    reducer = {"max": jnp.max, "add": jnp.sum, "avg": jnp.sum}.get(kind)
    if reducer is None:
        raise NotImplementedError(f"pool payload {kind}")
    count = 1
    for ax in range(x.ndim - 1, -1, -1):
        f = window[ax]
        if f <= 1:
            continue
        count *= f
        shp = x.shape
        assert shp[ax] % f == 0, (shp, window)
        x = x.reshape(shp[:ax] + (shp[ax] // f, f) + shp[ax + 1:])
        x = reducer(x, axis=ax + 1)
    if kind == "avg":
        x = _div_exact(x, count)
    return x


def maxpool2d(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """Standalone NHWC max pool (VALID padding) — the unfused oracle the
    conv+pool fusion pass is checked against."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        init = jnp.iinfo(x.dtype).min
    else:
        init = -jnp.inf
    return lax.reduce_window(
        x, init, lax.max,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def avgpool2d(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """Standalone NHWC average pool (VALID padding): window ADDs in the
    accumulator dtype, then the shared DIV exit path — the unfused
    oracle the conv+avg-pool fusion is checked against."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        acc = x.astype(jnp.int32)
        init = jnp.int32(0)
    else:
        acc = x.astype(jnp.float32)
        init = jnp.float32(0)
    summed = lax.reduce_window(
        acc, init, lax.add,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )
    return _div_exact(summed, kh * kw)


def apply_epilogue(out: jax.Array, epilogue, env) -> jax.Array:
    """Apply a chain of :class:`repro.core.ir.FusedEpilogue` entries
    (duck-typed: ``kind`` / ``operand`` / ``window`` attributes)."""
    for e in epilogue:
        window = getattr(e, "window", ())
        if window:
            out = pool_reduce(e.kind, out, window)
        elif e.operand is None:
            out = unary(e.kind, out)
        else:
            out = binary(e.kind, out, env[e.operand])
    return out


# ---------------------------------------------------------------------------
# multi-head / grouped-query attention
# ---------------------------------------------------------------------------


def attention(
    q: jax.Array,          # (B, Hq, Sq, D)
    k: jax.Array,          # (B, Hkv, Sk, D)
    v: jax.Array,          # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """GQA attention oracle.  Hq must be a multiple of Hkv; q_offset is the
    absolute position of q[0] (decode: q_offset = cache_len)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, sq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(sk)[None, :]
        mask = qpos >= kpos
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# fused (optionally gated) MLP
# ---------------------------------------------------------------------------


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    if name == "squared_relu":
        r = jnp.maximum(x, 0.0)
        return r * r
    raise ValueError(name)


def mlp(
    x: jax.Array,            # (M, D)
    w_gate: jax.Array | None,  # (D, F) or None for ungated
    w_up: jax.Array,         # (D, F)
    w_down: jax.Array,       # (F, D)
    *,
    act: str = "silu",
) -> jax.Array:
    """out = (act(x@Wg) * (x@Wu)) @ Wd, or act(x@Wu)@Wd when ungated.
    Accumulation in fp32, cast back to x.dtype."""
    xf = x.astype(jnp.float32)
    up = xf @ w_up.astype(jnp.float32)
    if w_gate is not None:
        gate = _act(act, xf @ w_gate.astype(jnp.float32))
        h = gate * up
    else:
        h = _act(act, up)
    out = h @ w_down.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality), sequential-scan oracle
# ---------------------------------------------------------------------------


def ssd(
    x: jax.Array,        # (B, L, H, P)
    dt: jax.Array,       # (B, L, H)      softplus-activated step sizes
    a: jax.Array,        # (H,)           negative decay rates (A = -exp(a_log))
    b_mat: jax.Array,    # (B, L, N)      input gate (ngroups=1)
    c_mat: jax.Array,    # (B, L, N)      output gate (ngroups=1)
    *,
    init_state: jax.Array | None = None,   # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Exact recurrence (arXiv:2405.21060 Eq. SSD):

        S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t ⊗ b_t
        y_t = S_t @ c_t

    Returns (y (B,L,H,P), final_state (B,H,P,N)).  O(L) scan — oracle only.
    """
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    s0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = b_mat.astype(jnp.float32)
    cf = c_mat.astype(jnp.float32)

    def step(state, t):
        dt_t = dtf[:, t]                          # (B, H)
        decay = jnp.exp(dt_t * af[None, :])       # (B, H)
        upd = jnp.einsum(
            "bhp,bn->bhpn", xf[:, t] * dt_t[..., None], bf[:, t]
        )
        state = state * decay[..., None, None] + upd
        y_t = jnp.einsum("bhpn,bn->bhp", state, cf[:, t])
        return state, y_t

    final, ys = lax.scan(step, s0, jnp.arange(l))
    y = jnp.moveaxis(ys, 0, 1)                    # (B, L, H, P)
    return y.astype(x.dtype), final


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b_mat: jax.Array,
    c_mat: jax.Array,
    *,
    chunk: int = 16,
    init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD (the algorithm the Pallas kernel implements): intra-chunk
    quadratic term + inter-chunk state carry.  Must match :func:`ssd`."""
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    assert l % chunk == 0, "oracle requires chunk | L"
    nc = l // chunk
    xf = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    af = a.astype(jnp.float32)
    bf = b_mat.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cf = c_mat.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    # per-position log decay within a chunk: cum_t = sum_{i<=t} dt_i * a
    da = dtf * af[None, None, None, :]                 # (B,NC,Q,H)
    cum = jnp.cumsum(da, axis=2)                       # inclusive
    s0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )

    def chunk_step(state, ci):
        xq, dq, bq, cq = xf[:, ci], dtf[:, ci], bf[:, ci], cf[:, ci]
        cumq = cum[:, ci]                              # (B,Q,H)
        # intra-chunk: y_intra[t] = sum_{s<=t} exp(cum_t - cum_s) dt_s (c_t·b_s) x_s
        rel = cumq[:, :, None, :] - cumq[:, None, :, :]      # (B,Q,Q,H) t,s
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        gate = jnp.where(tri[None, :, :, None], jnp.exp(rel), 0.0)
        cb = jnp.einsum("btn,bsn->bts", cq, bq)              # (B,Q,Q)
        w = cb[..., None] * gate * dq[:, None, :, :]          # (B,t,s,H)
        y_intra = jnp.einsum("btsh,bshp->bthp", w, xq)
        # inter-chunk: contribution of carried state
        dec_t = jnp.exp(cumq)                                 # (B,Q,H)
        y_inter = jnp.einsum(
            "bqn,bhpn,bqh->bqhp", cq, state, dec_t
        )
        # state update: S' = exp(cum_Q) * S + sum_s exp(cum_Q - cum_s) dt_s x_s ⊗ b_s
        dec_chunk = jnp.exp(cumq[:, -1])                      # (B,H)
        carry_gate = jnp.exp(cumq[:, -1, None, :] - cumq)     # (B,Q,H)
        upd = jnp.einsum(
            "bqhp,bqn->bhpn", xq * (dq * carry_gate)[..., None], bq
        )
        state = state * dec_chunk[..., None, None] + upd
        return state, y_intra + y_inter

    final, ys = lax.scan(chunk_step, s0, jnp.arange(nc))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, l, h, p)
    return y.astype(x.dtype), final


def ssd_decode_step(
    state: jax.Array,    # (B, H, P, N)
    x_t: jax.Array,      # (B, H, P)
    dt_t: jax.Array,     # (B, H)
    a: jax.Array,        # (H,)
    b_t: jax.Array,      # (B, N)
    c_t: jax.Array,      # (B, N)
) -> tuple[jax.Array, jax.Array]:
    """Single-token recurrent step (decode path)."""
    sf = state.astype(jnp.float32)
    decay = jnp.exp(dt_t.astype(jnp.float32) * a.astype(jnp.float32)[None])
    upd = jnp.einsum(
        "bhp,bn->bhpn",
        x_t.astype(jnp.float32) * dt_t.astype(jnp.float32)[..., None],
        b_t.astype(jnp.float32),
    )
    new = sf * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new, c_t.astype(jnp.float32))
    return y.astype(x_t.dtype), new
