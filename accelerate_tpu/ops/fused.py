"""Fused Pallas step kernels: the attention prologue and the optimizer
epilogue — the two ends of the compiled train step that XLA leaves as
elementwise op soup between the big matmuls.

Prologue (``fused_qkv_prologue``): RMSNorm -> QKV projection -> rope ->
head split in ONE kernel. The unfused chain (models/transformer.py:
``RMSNorm.__call__`` + three ``nn.Dense`` + two ``rope`` calls)
materializes the normalized activations and three pre-rope projections
in HBM; here the norm is recomputed per weight tile in registers, the
three projection matmuls run against one concatenated (E, (H+2*Hkv)*D)
weight block, and the rotation is applied before the tile ever leaves
VMEM. Backward follows flash attention's precedent (PERF.md section 6,
PRs 21-25): a hand-fused backward was measured far slower, so the vjp is
``jax.vjp`` of the plain-JAX reference chain (``prologue_reference``,
numerically the exact module-path math).

Epilogue (``fused_adamw`` + ``maybe_fused_epilogue``): the per-leaf
tail of ``_sync_apply`` — global-norm clip multiply, adamw moment
update, bias correction, weight decay, parameter apply, and the
non-finite hold — as one elementwise Pallas kernel per leaf (~12 XLA
HLO ops fused to one launch, no intermediate leaf-sized buffers). The
contract is BITWISE fp32 parity with the optax chain
(scale_by_adam -> add_decayed_weights -> scale_by_learning_rate ->
apply_updates); every expression below mirrors the optax 0.2.x source
order exactly. The mean/unscale/global-norm head of ``_sync_apply``
stays outside (global_norm's reduction order must not change), as does
the ZeRO shard pin (``_pin_to_shardings`` — a sharding constraint, not
arithmetic).

No CPU fallback: every ``pallas_call`` here is lowered for Mosaic. CPU
tests run the same kernels under
``ops.flash_attention.kernel_interpret_mode()``.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import MIN_BLOCK, fit_block, kernels_interpreted

__all__ = [
    "fused_qkv_prologue",
    "prologue_reference",
    "prologue_supported",
    "rms_norm_reference",
    "rope_inv_freqs",
    "fused_adamw",
    "FusedAdamW",
    "maybe_fused_epilogue",
    "adamw_epilogue_reference",
]

LANES = 128  # TPU vector lane width — minor-dim tile granularity
# Mosaic's default scoped-VMEM limit is 16 MiB (v5e); tiles are sized to
# leave headroom for the compiler's own temporaries
VMEM_TILE_BUDGET = 14 * 2**20


# ---------------------------------------------------------------------- #
# fused prologue: RMSNorm -> QKV -> rope -> head split
# ---------------------------------------------------------------------- #
def rope_inv_freqs(head_dim: int, theta: float, scaling: Optional[dict]) -> jax.Array:
    """(D/2,) f32 inverse frequencies, scaled exactly like ``rope()``."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    from ..models.transformer import _scale_rope_freqs

    return _scale_rope_freqs(freqs, scaling, theta)


def rms_norm_reference(x, scale, *, eps: float, norm_offset: bool):
    """RMSNorm.__call__'s math on an explicit scale param — used when a
    Block handed Attention the raw residual stream + norm scale but the
    fused kernel doesn't support the shape, so the norm must be applied
    the plain way before the unfused projections."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    mult = (1.0 + scale) if norm_offset else scale
    return (y * mult).astype(x.dtype)


def _rope_tables(positions, inv_freqs):
    """(rows, D) duplicated cos/sin tables for the rotate-half identity:
    [x1*cos - x2*sin, x2*cos + x1*sin] == x*[cos,cos] + [-x2,x1]*[sin,sin]
    (IEEE-exact: a - b == a + (-b))."""
    angles = positions.reshape(-1, 1).astype(jnp.float32) * inv_freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (
        jnp.concatenate([cos, cos], axis=-1),
        jnp.concatenate([sin, sin], axis=-1),
    )


def _rope_apply_tables(x, cosd, sind):
    """Rotate with precomputed (rows, D) tables; x is (B, S, H, D)."""
    b, s, _, d = x.shape
    cos = cosd.reshape(b, s, 1, d)
    sin = sind.reshape(b, s, 1, d)
    xf = x.astype(jnp.float32)
    half = d // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def _prologue_reference_tables(
    x, scale, wq, wk, wv, bq, bk, bv, cosd, sind,
    *, eps: float, norm_offset: bool,
    num_heads: int, num_kv_heads: int, head_dim: int, dtype,
):
    b, s = x.shape[:2]
    xn = rms_norm_reference(x, scale, eps=eps, norm_offset=norm_offset)

    def dense(w, bias):
        # nn.Dense promotes inputs/kernel/bias to module dtype, then
        # dot_general + bias add
        y = jax.lax.dot_general(
            xn.astype(dtype), w.astype(dtype), (((xn.ndim - 1,), (0,)), ((), ()))
        )
        if bias is not None:
            y = y + bias.astype(dtype)
        return y

    q = dense(wq, bq).reshape(b, s, num_heads, head_dim)
    k = dense(wk, bk).reshape(b, s, num_kv_heads, head_dim)
    v = dense(wv, bv).reshape(b, s, num_kv_heads, head_dim)
    q = _rope_apply_tables(q, cosd, sind)
    k = _rope_apply_tables(k, cosd, sind)
    return q, k, v


def prologue_reference(
    x, scale, wq, wk, wv, bq, bk, bv, positions, inv_freqs,
    *, eps: float, norm_offset: bool,
    num_heads: int, num_kv_heads: int, head_dim: int, dtype,
):
    """Plain-JAX prologue: the exact math of the unfused module chain
    (RMSNorm -> nn.Dense q/k/v -> reshape -> rope on q,k). Serves as the
    parity anchor in tests and as the backward for the Pallas kernel."""
    cosd, sind = _rope_tables(positions, inv_freqs)
    return _prologue_reference_tables(
        x, scale, wq, wk, wv, bq, bk, bv, cosd, sind,
        eps=eps, norm_offset=norm_offset, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim, dtype=dtype,
    )


def _col_blocks(num_heads: int, num_kv_heads: int, head_dim: int) -> list[int]:
    """Candidate weight-column tiles, widest first: each is <= 512, a
    whole number of heads, and divides both the q and k/v column spans —
    so no tile straddles the q/k/v boundaries and the rope predicate is
    uniform per tile."""
    g = math.gcd(num_heads, num_kv_heads)
    return sorted(
        (m * head_dim for m in range(1, g + 1)
         if g % m == 0 and m * head_dim <= 512),
        reverse=True,
    ) or [head_dim]


def _prologue_vmem_bytes(br, c, hidden, head_dim, x_bytes, w_bytes, out_bytes):
    """Scoped VMEM one grid step needs: Pallas double-buffers every
    in/out tile, and the body holds an f32 copy of the x tile plus a
    handful of (br, c) f32 temporaries (acc, rotated, roped, select)."""
    pipelined = 2 * (
        br * hidden * x_bytes + hidden * c * w_bytes + br * c * out_bytes
        + 2 * br * head_dim * 4 + hidden * 4
    )
    body = br * hidden * 4 + 4 * br * c * 4
    return pipelined + body


def _prologue_tiles(
    rows, hidden, num_heads, num_kv_heads, head_dim,
    x_bytes, w_bytes, out_bytes,
) -> tuple[int, int]:
    """(row block, column block) chosen from the shape so the step fits
    scoped VMEM: the widest column tile that fits at the preferred 256-row
    block, halving the row block only when no column tile does. With fp32
    master weights at hidden 4096 this lands on (256, 128); the historical
    fixed (256, 512) tile needed 20.5 MiB of Mosaic's 16."""
    br = fit_block(rows, 256)
    while br is not None:
        for c in _col_blocks(num_heads, num_kv_heads, head_dim):
            need = _prologue_vmem_bytes(
                br, c, hidden, head_dim, x_bytes, w_bytes, out_bytes
            )
            if need <= VMEM_TILE_BUDGET:
                return br, c
        br = fit_block(rows, br // 2) if br > MIN_BLOCK else None
    raise ValueError(
        f"fused prologue: no (row, column) tile of a ({rows}, {hidden}) "
        f"activation fits {VMEM_TILE_BUDGET} bytes of VMEM"
    )


def prologue_supported(
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    batch: int,
    seq: int,
    hidden: int,
) -> bool:
    """Alignment gate for the fused prologue: shapes Mosaic cannot tile at
    all take the unfused module chain."""
    if head_dim % 2:
        return False  # rope pairs i with i + D/2
    rows = batch * seq
    if fit_block(rows, 256) is None:
        return False
    if kernels_interpreted():
        return True  # the interpreter has no tiling constraints
    # Mosaic: (8, 128) tile granularity on every block minor dim — hidden
    # (x / weight rows) and head_dim (cos/sin, the in-tile head reshape,
    # and every column tile, a whole number of heads)
    return hidden % LANES == 0 and head_dim % LANES == 0


def _prologue_call(
    x2d, scale, wqkv, bqkv, cosd, sind,
    *, eps: float, norm_offset: bool, head_dim: int, row_block: int,
    col_block: int, rope_cols: int, dtype,
):
    """One pallas_call over the flattened (rows, E) activations and the
    concatenated (E, W) qkv weight. Grid (rows/br, W/c), col-minor — the
    x tile stays resident across the j sweep."""
    rows, hidden = x2d.shape
    width = wqkv.shape[1]
    br = row_block
    c = col_block
    d = head_dim
    has_bias = bqkv is not None

    def kernel(*refs):
        if has_bias:
            x_ref, s_ref, w_ref, b_ref, cos_ref, sin_ref, o_ref = refs
        else:
            x_ref, s_ref, w_ref, cos_ref, sin_ref, o_ref = refs
        j = pl.program_id(1)
        # RMSNorm in f32, recomputed per weight tile (one rsqrt + two
        # multiplies per element — cheap next to the matmul it feeds)
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        sc = s_ref[...]
        mult = (1.0 + sc) if norm_offset else sc
        xn = (y * mult).astype(dtype)
        w = w_ref[...].astype(dtype)
        acc = jax.lax.dot_general(
            xn, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        proj = acc.astype(dtype)
        if has_bias:
            proj = proj + b_ref[...].astype(dtype)
        # rope via the rotate-half identity: [x1*cos - x2*sin,
        # x2*cos + x1*sin] == x * [cos,cos] + [-x2, x1] * [sin,sin]
        pf = proj.astype(jnp.float32).reshape(br, c // d, d)
        cos = cos_ref[...][:, None, :]
        sin = sin_ref[...][:, None, :]
        half = d // 2
        x1, x2 = pf[..., :half], pf[..., half:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        roped = (pf * cos + rot * sin).reshape(br, c)
        flat = pf.reshape(br, c)
        # col tiles never straddle the q/k/v boundaries (col_block
        # divides both spans), so the predicate is uniform per tile
        o_ref[...] = jnp.where(j * c < rope_cols, roped, flat).astype(dtype)

    in_specs = [
        pl.BlockSpec((br, hidden), lambda i, j: (i, 0)),
        pl.BlockSpec((1, hidden), lambda i, j: (0, 0)),
        pl.BlockSpec((hidden, c), lambda i, j: (0, j)),
    ]
    operands = [x2d, scale.reshape(1, hidden)]
    operands.append(wqkv)
    if has_bias:
        in_specs.append(pl.BlockSpec((1, c), lambda i, j: (0, j)))
        operands.append(bqkv.reshape(1, width))
    in_specs += [
        pl.BlockSpec((br, d), lambda i, j: (i, 0)),
        pl.BlockSpec((br, d), lambda i, j: (i, 0)),
    ]
    operands += [cosd, sind]
    return pl.pallas_call(
        kernel,
        grid=(rows // br, width // c),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        interpret=kernels_interpreted(),
    )(*operands)


def _pin_head_dim(x):
    """rope()'s sharding guard: pin head_dim unsplit through the rotation
    (see models/transformer.py rope() for the SPMD failure it prevents)."""
    from ..parallel.sharding import live_mesh

    mesh = live_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec(*([PartitionSpec.UNCONSTRAINED] * (x.ndim - 1)), None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def fused_qkv_prologue(
    x, scale, wq, wk, wv, bq, bk, bv, positions,
    *, eps: float, norm_offset: bool,
    num_heads: int, num_kv_heads: int, head_dim: int,
    theta: float, scaling: Optional[dict] = None,
    dtype=jnp.float32,
):
    """Fused RMSNorm -> QKV -> rope -> head split.

    Inputs are the raw residual stream ``x (B,S,E)``, the norm ``scale
    (E,)``, the three projection kernels ``(E, H*D)/(E, Hkv*D)`` (+
    optional biases), and ``positions (B,S)``. Returns ``q (B,S,H,D)``,
    ``k/v (B,S,Hkv,D)`` — bit-compatible with the unfused module chain
    in fp32. Backward is ``jax.vjp`` of ``prologue_reference`` (XLA's
    backward beats a hand kernel here, and the reference IS the parity
    definition)."""
    b, s, hidden = x.shape
    d = head_dim
    rows = b * s
    q_cols = num_heads * d
    kv_cols = num_kv_heads * d
    rope_cols = q_cols + kv_cols  # q and k rotate; v passes through
    row_block, col_block = _prologue_tiles(
        rows, hidden, num_heads, num_kv_heads, d,
        x.dtype.itemsize, wq.dtype.itemsize, jnp.dtype(dtype).itemsize,
    )
    statics = dict(
        eps=eps, norm_offset=norm_offset, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=d, dtype=dtype,
    )
    # cos/sin tables computed OUTSIDE the custom_vjp and passed as plain
    # args: closing over traced values (positions under nn.scan) leaks
    # tracers into the backward trace. Their cotangent is zero — the
    # unfused chain treats cos/sin as constants of integer positions too.
    inv_freqs = rope_inv_freqs(d, theta, scaling)
    cosd, sind = _rope_tables(positions, inv_freqs)

    @jax.custom_vjp
    def run(x, scale, wq, wk, wv, bq, bk, bv, cosd, sind):
        x2d = x.reshape(rows, hidden)
        wqkv = jnp.concatenate([wq, wk, wv], axis=1)
        bqkv = (
            jnp.concatenate([bq, bk, bv]) if bq is not None else None
        )
        out = _prologue_call(
            x2d, scale, wqkv, bqkv, cosd, sind,
            eps=eps, norm_offset=norm_offset, head_dim=d,
            row_block=row_block, col_block=col_block, rope_cols=rope_cols,
            dtype=dtype,
        )
        q = out[:, :q_cols].reshape(b, s, num_heads, d)
        k = out[:, q_cols:rope_cols].reshape(b, s, num_kv_heads, d)
        v = out[:, rope_cols:].reshape(b, s, num_kv_heads, d)
        return q, k, v

    def fwd(*args):
        return run(*args), args

    def bwd(res, cts):
        *diff_args, cosd, sind = res
        ref = functools.partial(_prologue_reference_tables, **statics)
        _, vjp = jax.vjp(lambda *a: ref(*a, cosd, sind), *diff_args)
        grads = vjp(cts)
        return (*grads, jnp.zeros_like(cosd), jnp.zeros_like(sind))

    run.defvjp(fwd, bwd)
    q, k, v = run(x, scale, wq, wk, wv, bq, bk, bv, cosd, sind)
    return _pin_head_dim(q), _pin_head_dim(k), v


# ---------------------------------------------------------------------- #
# fused optimizer epilogue
# ---------------------------------------------------------------------- #
class FusedAdamW(optax.GradientTransformation):
    """An ``optax.GradientTransformation`` (same (init, update) pair —
    isinstance-compatible with AcceleratedOptimizer's check) that also
    carries the static hyperparameters the fused epilogue kernel needs.
    ``update`` IS real ``optax.adamw``'s, so every non-fused consumer
    (eager ``apply_gradients``, state-sharding inference, fallback
    paths) stays exact."""


def fused_adamw(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    eps_root: float = 0.0,
    weight_decay: float = 1e-4,
    *,
    fused: Optional[bool] = None,
) -> FusedAdamW:
    """adamw whose ``_sync_apply`` epilogue runs as one Pallas kernel per
    leaf. State layout and numerics are identical to
    ``optax.adamw(learning_rate, b1, b2, eps, eps_root,
    weight_decay=weight_decay)`` — checkpoints interchange, and any step
    taken through the unfused path is bitwise the same in fp32.

    ``fused=None`` reads ACCELERATE_TPU_FUSED_EPILOGUE (default on —
    constructing this transform is already the opt-in)."""
    base = optax.adamw(
        learning_rate, b1=b1, b2=b2, eps=eps, eps_root=eps_root,
        weight_decay=weight_decay,
    )
    t = FusedAdamW(base.init, base.update)
    t.hyperparams = dict(
        learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
        eps_root=eps_root, weight_decay=weight_decay,
    )
    if fused is None:
        fused = os.environ.get("ACCELERATE_TPU_FUSED_EPILOGUE", "1") not in (
            "0", "false", "False",
        )
    t.fused = bool(fused)
    return t


def _adamw_leaf_kernel(
    g, p, mu, nu, scalars,
    *, b1, b2, eps, eps_root, weight_decay,
):
    """One elementwise kernel for a single leaf: adam moment update ->
    bias correction -> weight decay -> lr scale -> apply -> finite hold.
    Mirrors the optax op ORDER exactly (bitwise fp32). The clip multiply
    stays with the CALLER (pre-clipped grads come in): folding it into
    the kernel hands LLVM a three-multiply chain whose fma contraction
    order differs from the unfused program's — a 1-ulp mu divergence
    that breaks the bitwise contract (measured on XLA:CPU)."""
    shape, n = p.shape, p.size
    pad = (-n) % (MIN_BLOCK * LANES)
    padded = n + pad

    def flat(a):
        a = a.reshape(-1)
        return jnp.pad(a, (0, pad)).reshape(padded // LANES, LANES)

    rows = padded // LANES
    br = fit_block(rows, 256)

    def kernel(scal_ref, g_ref, p_ref, mu_ref, nu_ref,
               po_ref, muo_ref, nuo_ref):
        g = g_ref[...]
        p = p_ref[...]
        mu = mu_ref[...]
        nu = nu_ref[...]
        # scale_by_adam: update_moment / update_moment_per_elem_norm
        mu2 = (1 - b1) * g + b1 * mu
        nu2 = (1 - b2) * (g ** 2) + b2 * nu
        # tree_bias_correction: t / (1 - decay**count_inc)
        mu_hat = mu2 / scal_ref[0, 1]
        nu_hat = nu2 / scal_ref[0, 2]
        u = mu_hat / (jnp.sqrt(nu_hat + eps_root) + eps)
        # add_decayed_weights, then scale_by_learning_rate (-lr * u)
        u = u + weight_decay * p
        u = scal_ref[0, 3] * u
        newp = p + u
        fin = scal_ref[0, 4] != 0.0
        po_ref[...] = jnp.where(fin, newp, p)
        muo_ref[...] = jnp.where(fin, mu2, mu)
        nuo_ref[...] = jnp.where(fin, nu2, nu)

    scal_spec = pl.BlockSpec(
        (1, 8), lambda i: (0, 0), memory_space=pltpu.SMEM
    )
    leaf_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[scal_spec] + [leaf_spec] * 4,
        out_specs=[leaf_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * 3,
        interpret=kernels_interpreted(),
    )(scalars, flat(g), flat(p), flat(mu), flat(nu))
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)


def adamw_epilogue_reference(
    grads, params, mu, nu, count, *, hp, clip_scale, finite, step_size,
):
    """The unfused optax chain, spelled out — what the kernel must match
    bitwise. Used by tests; `_sync_apply`'s own fallback path is the real
    optax transform, which this mirrors expression-for-expression."""
    b1, b2 = hp["b1"], hp["b2"]
    eps, eps_root, wd = hp["eps"], hp["eps_root"], hp["weight_decay"]
    if clip_scale is not None:
        grads = jax.tree.map(lambda g: g * clip_scale, grads)
    count_inc = optax.safe_int32_increment(count)
    mu2 = jax.tree.map(lambda g, m: (1 - b1) * g + b1 * m, grads, mu)
    nu2 = jax.tree.map(lambda g, v: (1 - b2) * (g ** 2) + b2 * v, grads, nu)
    bc1 = 1 - b1 ** count_inc
    bc2 = 1 - b2 ** count_inc
    mu_hat = jax.tree.map(lambda t: t / bc1.astype(t.dtype), mu2)
    nu_hat = jax.tree.map(lambda t: t / bc2.astype(t.dtype), nu2)
    updates = jax.tree.map(
        lambda m, v: m / (jnp.sqrt(v + eps_root) + eps), mu_hat, nu_hat
    )
    updates = jax.tree.map(lambda g, p: g + wd * p, updates, params)
    updates = jax.tree.map(lambda g: step_size * g, updates)
    new_params = jax.tree.map(
        lambda p, u: jnp.asarray(p + u).astype(jnp.asarray(p).dtype),
        params, updates,
    )
    hold = lambda n, o: jnp.where(finite, n, o)
    return (
        jax.tree.map(hold, new_params, params),
        jax.tree.map(hold, mu2, mu),
        jax.tree.map(hold, nu2, nu),
        jnp.where(finite, count_inc, count),
    )


def maybe_fused_epilogue(
    opt_transform, grads, opt_state, params,
    *, clip_scale, finite,
):
    """Run the fused adamw epilogue if ``opt_transform`` opted in and the
    state matches the layout this kernel understands; else None and the
    caller takes the existing optax path. Replaces exactly the
    clip-mult -> update -> apply_updates -> finite-hold tail of
    ``_sync_apply`` — mean/unscale/global-norm stay with the caller."""
    hp = getattr(opt_transform, "hyperparams", None)
    if not isinstance(hp, dict) or not getattr(opt_transform, "fused", False):
        return None
    if not (
        isinstance(opt_state, tuple)
        and len(opt_state) == 3
        and isinstance(opt_state[0], optax.ScaleByAdamState)
    ):
        return None
    adam = opt_state[0]
    leaves = (
        jax.tree.leaves(params) + jax.tree.leaves(grads)
        + jax.tree.leaves(adam.mu) + jax.tree.leaves(adam.nu)
    )
    if not all(l.dtype == jnp.float32 for l in leaves):
        return None  # the bitwise contract is scoped to fp32 trees

    if clip_scale is not None:
        # the clip multiply stays OUTSIDE the kernel, exactly where the
        # unfused chain applies it (see _adamw_leaf_kernel docstring)
        grads = jax.tree.map(lambda g: g * clip_scale, grads)
    count_inc = optax.safe_int32_increment(adam.count)
    lr = hp["learning_rate"]
    if callable(lr):
        sched = opt_state[2]
        if not isinstance(sched, optax.ScaleByScheduleState):
            return None
        step_size = -lr(sched.count)
    else:
        step_size = jnp.asarray(-lr, jnp.float32)
    bc1 = 1 - hp["b1"] ** count_inc
    bc2 = 1 - hp["b2"] ** count_inc
    scalars = jnp.stack(
        [
            jnp.float32(0.0),  # reserved
            bc1, bc2, step_size,
            jnp.asarray(finite, jnp.float32),
            jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
        ]
    ).astype(jnp.float32).reshape(1, 8)

    leaf = functools.partial(
        _adamw_leaf_kernel,
        scalars=scalars,
        b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
        eps_root=hp["eps_root"], weight_decay=hp["weight_decay"],
    )
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(adam.mu)
    flat_nu = jax.tree.leaves(adam.nu)
    outs = [leaf(g, p, m, v) for g, p, m, v in
            zip(flat_g, flat_p, flat_mu, flat_nu)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_mu = jax.tree.unflatten(treedef, [o[1] for o in outs])
    new_nu = jax.tree.unflatten(treedef, [o[2] for o in outs])

    new_adam = optax.ScaleByAdamState(
        count=jnp.where(finite, count_inc, adam.count), mu=new_mu, nu=new_nu
    )
    tail = opt_state[2]
    if isinstance(tail, optax.ScaleByScheduleState):
        tail = optax.ScaleByScheduleState(
            count=jnp.where(
                finite, optax.safe_int32_increment(opt_state[2].count),
                opt_state[2].count,
            )
        )
    return new_params, (new_adam, opt_state[1], tail)
