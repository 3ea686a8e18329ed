"""Pallas TPU kernels for the hot ops.

``histogram_tpu``: XGBoost-style gradient-histogram accumulation — the
per-row scatter-add the reference's use case feeds into its allreduce
(doc/guide.md:137-143). TPUs have no hardware scatter, so the kernel
reformulates the scatter as masked matmuls on the MXU through a
TWO-LEVEL bin decomposition, bin = hi*128 + lo:

- entries on lanes: the kernel reads the flat form as handed, the
  shard's [1, n] bins, grad and hess, and a grid step takes its block
  as one row of entries, [1, chunk]. Nothing pads or copies the inputs
  first: the last block runs past n, and its entries past n are masked
  inside the kernel, so no input copy precedes it;
- the lo one-hot [128, chunk] (lo values on sublanes) and the hi match
  [atile, chunk] cost O(128 + atile) compares an entry instead of the
  naive one-hot's O(nbins); the component values fuse into the hi side,
  [r*atile, chunk] (block k: component k where hi matches), with no lane
  padding;
- ONE NT dot [r*atile, chunk] x [128, chunk]^T -> [r*atile, 128] per
  step stacks every component on the MXU;
- the a-tile is sized from nbins and r so that r*atile <= 512: HIGGS's
  7,168 bins (56 hi groups) is one a-tile; wider histograms take more
  a-tiles, each reading the rows again;
- default ``precision="high"``: the kernel splits each loaded block of
  grad and hess into bf16 hi parts and f32 remainders, four components
  recombined after the kernel, ~1e-6 relative accuracy;
  ``precision="fast"``: two components (grad, hess) cast to bf16, ~1e-3.

On a TPU v5e the kernel builds one HIGGS rank's histogram (36,750,000
entries at 7,168 bins, ``precision="high"``) in about 11.1 ms (the
benchmark's ``hist_kernel_ms`` in its ``higgs_level`` cell).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


_CHUNK = 16384   # entries per grid step
_LANES = 128     # lanes of a vreg; lo one-hot of 128 bins
_FUSED_ROWS = 512   # bound on the fused operand's r*atile rows (MXU, VMEM)
# the build kernel's name on a device trace (the HLO custom call takes
# the pallas_call's name): part of the interface, read by the benchmark
HIST_KERNEL_NAME = "rabit_hist_build"


def _out_struct(shape, dtype, *arrs):
    """``ShapeDtypeStruct`` whose varying-manual-axes (vma) is the union
    of the inputs' — required for pallas_call outputs under a
    ``check_vma=True`` shard_map; a plain struct elsewhere."""
    vma = set()
    for a in arrs:
        vma |= set(jax.typeof(a).vma)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _atiles(nbins: int, r: int) -> tuple[int, int]:
    """(atile, nat): hi groups per a-tile, a multiple of 8, and the
    number of a-tiles, so that the fused operand's r*atile rows stay
    within _FUSED_ROWS: 7,168 bins is one a-tile of 56, 16,640 two of 72
    at r = 4."""
    adim = -(-nbins // _LANES)
    nat = -(-adim // (_FUSED_ROWS // r))
    return -(-adim // (8 * nat)) * 8, nat


def _hist_kernel_body(n: int, precision: str, atile: int, chunk: int,
                      b_ref, g_ref, h_ref, out_ref):
    from jax.experimental import pallas as pl

    j = pl.program_id(0)   # a-tile (outer)
    i = pl.program_id(1)   # entry block (inner: out block stays resident)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    cbits = _LANES.bit_length() - 1
    bb = b_ref[:]                                    # int32 [1, chunk]
    # the last block runs past n into memory no caller wrote: there hi is
    # -1, which matches no hi group, so the select that builds the fused
    # operand drops those weights, whatever they hold (NaN included)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) + i * chunk
    hi = jnp.where(idx < n, jax.lax.shift_right_logical(bb, cbits), -1)
    lo = jax.lax.bitwise_and(bb, _LANES - 1)         # bin = hi*128 + lo
    g, h = g_ref[:], h_ref[:]                        # f32 [1, chunk]
    comps = (g, h)
    if precision == "high":
        # the split of _components, made on the loaded block; no XLA
        # excess-precision folding reaches a Mosaic body, so the bf16
        # round-trip needs no barrier here (its truncf/extf pair is in
        # the lowered kernel: tests/test_tpu_compile.py)
        g_hi = g.astype(jnp.bfloat16).astype(jnp.float32)
        h_hi = h.astype(jnp.bfloat16).astype(jnp.float32)
        comps = (g_hi, h_hi, g - g_hi, h - h_hi)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (_LANES, chunk), 0)
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (atile, chunk), 0) \
        + j * atile
    # out[k*atile + a, c] = sum_entries [hi==a] * [lo==c] * comp_k, with
    # the entries on lanes: the lo one-hot [128, chunk] (lo values on
    # sublanes), the hi match [atile, chunk], and the fused [r*atile,
    # chunk] (block k: comp_k where hi == a) for ONE NT dot. Fusing
    # value*mask stays exact in bf16 for the hi parts, which are
    # bf16-representable, the mask 0/1.
    onehot = (lo == iota_c).astype(jnp.bfloat16)
    h_match = hi == iota_a
    fused = jnp.concatenate(
        [jnp.where(h_match, c, 0.0) for c in comps], axis=0)
    out_ref[:] += jax.lax.dot_general(
        fused.astype(jnp.bfloat16), onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [r*atile, 128]


def _hist_compiler_params():
    """Mosaic params of both histogram kernels: the a-tile (flat) or
    feature-tile (dense) grid axis writes disjoint output blocks
    (parallel), the row-chunk axis accumulates (arbitrary). The scoped
    vmem budget is raised from v5e's default 16 MB to 64 MB (the chip
    has 128 MB physical VMEM and the kernel is the only resident): the
    flat kernel's fused operand is r*atile x chunk f32 before its cast."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024,
    )


def _check_precision(precision: str) -> None:
    if precision not in ("fast", "high"):
        raise ValueError(f"precision must be 'fast' or 'high', "
                         f"got {precision!r}")


def _components(grad, hess, precision: str) -> tuple:
    """The weights as the dense kernel takes them: (grad, hess) at
    "fast", and at "high" their bf16 hi parts and f32 remainders (g_hi,
    h_hi, g_lo, h_lo), in the order the flat kernel makes them from its
    loaded block. They stay f32 outside the kernel (a 1D f32 array needs
    no lane padding)."""
    if precision != "high":
        return grad, hess
    # the barrier is load-bearing: under --xla_allow_excess_precision
    # XLA folds the bf16 round-trip to identity, turning lo into exact
    # zeros and silently degrading "high" to "fast"
    g_hi = jax.lax.optimization_barrier(
        grad.astype(jnp.bfloat16)).astype(jnp.float32)
    h_hi = jax.lax.optimization_barrier(
        hess.astype(jnp.bfloat16)).astype(jnp.float32)
    return g_hi, h_hi, grad - g_hi, hess - h_hi


def _recombined(parts, precision: str):
    """[..., r, m] sums per component -> [..., 2, m] (grad, hess): at
    "high" the hi and lo components' sums added."""
    if precision != "high":
        return parts
    return parts[..., :2, :] + parts[..., 2:, :]


@functools.partial(jax.jit,
                   static_argnames=("nbins", "precision", "interpret"))
def _histogram_tpu_impl(bins, grad, hess, nbins, precision, interpret):
    from jax.experimental import pallas as pl

    # [n] -> [1, n]: on the [n] view of a [1, n] shard the two reshapes
    # cancel, and the kernel reads the shard's arrays as handed
    bins, grad, hess = (a.reshape(1, -1) for a in (bins, grad, hess))
    n = bins.shape[1]
    r = 4 if precision == "high" else 2
    atile, nat = _atiles(nbins, r)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_body, n, precision, atile, _CHUNK),
        grid=(nat, pl.cdiv(n, _CHUNK)),
        in_specs=[pl.BlockSpec((1, _CHUNK), lambda j, i: (0, i))] * 3,
        out_specs=pl.BlockSpec((r * atile, _LANES), lambda j, i: (j, 0)),
        out_shape=_out_struct((nat * r * atile, _LANES), jnp.float32,
                              bins, grad, hess),
        compiler_params=_hist_compiler_params(),
        interpret=interpret,
        name=HIST_KERNEL_NAME,
    )(bins, grad, hess)
    # out[j*r*atile + k*atile + a, c] -> [r, nat*atile*128] -> [nbins, 2]
    comps = out.reshape(nat, r, atile * _LANES).transpose(1, 0, 2) \
        .reshape(r, -1)[:, :nbins]
    return _recombined(comps, precision).T               # [nbins, 2]


def histogram_tpu(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                  nbins: int, precision: str = "high") -> jax.Array:
    """Per-bin (sum_g, sum_h): [nbins, 2] of the flat form, ``bins``,
    ``grad`` and ``hess`` all [n] or all [1, n], any n. Entries whose bin
    id is >= nbins contribute nothing. ``precision``: "high" (default,
    hi/lo split, ~2e-6 rel err) or "fast" (bf16 components, ~2e-4).

    The interpret flag is part of the jit key here, so flipping
    ``RABIT_PALLAS_INTERPRET`` between calls retraces correctly; a jit'd
    *caller* that traced this function resolves the flag at its own
    trace time."""
    _check_precision(precision)
    return _histogram_tpu_impl(bins, grad, hess, nbins, precision,
                               _interpret())


# ---------------------------------------------------------------------------
# Dense build: XGBoost's dense data form, bins [n, F] of global ids
# f*max_bin + u with ONE (grad, hess) pair per row. The flat kernel above
# cannot tell an entry's feature, so on wide data it tests every entry
# against every bin (512,000 at epsilon's 2,000 x 256: 4,000 hi groups, 32
# a-tiles at high precision, the rows read 32 times). Here the grid walks
# feature tiles x row chunks and each entry meets only its own feature's
# max_bin bins, through the same two-level split (u = hi*128 + lo, hi < H =
# ceil(max_bin / 128)) with the values fused into the narrow hi side:
#
# - the kernel reads the bins as [F, n] (features on sublanes, rows on
#   lanes), so that a feature's ids are one row. That is the TPU's own
#   layout of a wide [n, F] int32 array (minor dim n: it pads n to 128,
#   not F), so the transpose before the kernel is a bitcast, not a copy;
# - per feature: the lo one-hot [128, chunk] (lo values on sublanes) and
#   the fused [r*H, chunk] (row k*H + h: component k where hi == h), one
#   NT dot -> [r*H, 128], accumulated into the feature's rows of the
#   resident output block [ftile, r*H, 128];
# - the weights arrive as [r*H, n_pad] (each component H times, so the
#   fused operand is one select against a row-index constant), read once
#   per row chunk and shared by every feature of the tile; they are never
#   broadcast to F entries in HBM;
# - nothing pads or copies the bins: the row tail and a last feature tile
#   past F are partial blocks. Rows past n carry zero weights (the
#   [r*H, n] weights are padded, not the bins); a feature row past F
#   yields only its own output rows, which lie past F and are sliced
#   off; an id outside its feature's [0, H*128) matches no hi.
# ---------------------------------------------------------------------------

_DENSE_CHUNK = 2048    # rows per grid step
_DENSE_FTILE = 128     # features per grid step
_DENSE_LO_BITS = 7     # lo one-hot of 1 << 7 = 128 bins: one lane tile
# the dense build kernel's name on a device trace
HIST_DENSE_KERNEL_NAME = "rabit_hist_build_dense"


def _hist_dense_kernel_body(rh: int, hdim: int, cbits: int, max_bin: int,
                            ftile: int, chunk: int, b_ref, w_ref, out_ref):
    from jax.experimental import pallas as pl

    j = pl.program_id(0)   # feature tile (outer)
    i = pl.program_id(1)   # row chunk (inner: out block stays resident)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    cdim = 1 << cbits
    w = w_ref[:]                                          # f32 [rh, chunk]
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (cdim, chunk), 0)
    hsel = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rh, chunk), 0), hdim)
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)

    def group(g, carry):
        # eight features a step: one aligned load of their id rows, made
        # feature-local (u = id - f*max_bin)
        f8 = pl.multiple_of(g * 8, 8)
        u8 = b_ref[pl.ds(f8, 8), :] - (iota_f + j * ftile + f8) * max_bin
        for t in range(8):
            u = u8[t:t + 1, :]                            # [1, chunk]
            lo = jax.lax.bitwise_and(u, cdim - 1)
            hi = jax.lax.shift_right_arithmetic(u, cbits)  # < 0: no match
            onehot = (lo == iota_c).astype(jnp.bfloat16)  # [cdim, chunk]
            fused = jnp.where(hi == hsel, w, 0.0).astype(jnp.bfloat16)
            out_ref[f8 + t] += jax.lax.dot_general(
                fused, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [rh, cdim]
        return carry

    jax.lax.fori_loop(0, ftile // 8, group, 0)


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "precision", "interpret"))
def _histogram_dense_impl(bins, grad, hess, max_bin, precision, interpret):
    from jax.experimental import pallas as pl

    n, nfeat = bins.shape
    comps = _components(grad, hess, precision)
    r = len(comps)                                        # 2 or 4
    cdim = 1 << _DENSE_LO_BITS
    hdim = -(-max_bin // cdim)
    rh = r * hdim
    chunk = min(_DENSE_CHUNK, -(-n // 128) * 128)
    nrc = -(-n // chunk)
    ftile = min(_DENSE_FTILE, -(-nfeat // 8) * 8)
    nft = -(-nfeat // ftile)
    w = jnp.repeat(jnp.stack(comps), hdim, axis=0)        # [rh, n]
    w = jnp.pad(w, ((0, 0), (0, nrc * chunk - n)))
    out = pl.pallas_call(
        functools.partial(_hist_dense_kernel_body, rh, hdim, _DENSE_LO_BITS,
                          max_bin, ftile, chunk),
        grid=(nft, nrc),
        in_specs=[pl.BlockSpec((ftile, chunk), lambda j, i: (j, i)),
                  pl.BlockSpec((rh, chunk), lambda j, i: (0, i))],
        out_specs=pl.BlockSpec((ftile, rh, cdim), lambda j, i: (j, 0, 0)),
        out_shape=_out_struct((nft * ftile, rh, cdim), jnp.float32,
                              bins, grad, hess),
        compiler_params=_hist_compiler_params(),
        interpret=interpret,
        name=HIST_DENSE_KERNEL_NAME,
    )(bins.T, w)
    # out[f, k*H + h, lo] -> [F, r, max_bin] -> [F*max_bin, 2]
    out = out[:nfeat].reshape(nfeat, r, hdim * cdim)[:, :, :max_bin]
    return _recombined(out, precision).transpose(0, 2, 1) \
        .reshape(nfeat * max_bin, 2)


def histogram_dense_tpu(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                        nbins: int, precision: str = "high") -> jax.Array:
    """Per-bin (sum_g, sum_h) [nbins, 2] of the dense form: ``bins`` int32
    [n, F] of global ids f*max_bin + u (max_bin = nbins // F), ``grad``
    and ``hess`` [n], each row's pair counted on every one of its F
    entries. Any n and F; ``precision`` as in :func:`histogram_tpu`."""
    _check_precision(precision)
    nfeat = bins.shape[1]
    if nbins % nfeat:
        raise ValueError(f"nbins {nbins} is not a multiple of the "
                         f"{nfeat} features")
    return _histogram_dense_impl(bins, grad, hess, nbins // nfeat,
                                 precision, _interpret())


def pallas_available() -> bool:
    """Pallas TPU kernels only run on a real TPU backend."""
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def _interpret() -> bool:
    """Run pallas in interpret mode (CPU emulation) — used by the test
    suite on the virtual CPU mesh; never on a real TPU backend."""
    import os
    return (os.environ.get("RABIT_PALLAS_INTERPRET") == "1"
            and not pallas_available())


# ---------------------------------------------------------------------------
# Flash-attention block kernel: one online-softmax accumulation step over a
# K/V block, the per-step compute of ring attention
# (parallel/ring_attention.py). The scores matmul and the p·V matmul both
# land on the MXU; the running max/denominator updates are VPU elementwise.
# Grid is over heads; each program holds one head's [T, D] query block and
# [S, D] K/V block in VMEM.
# ---------------------------------------------------------------------------

# Masking constant shared with the jnp block update (ring_attention
# imports it): large-negative instead of -inf keeps exp() exact zero
# without inf-inf = nan in masked rows.
NEG_INF = -1e30


def flash_block_available() -> bool:
    """The kernel path is used on a real TPU backend (any head_dim — Mosaic
    pads the lane dimension) or under interpret mode for tests."""
    return pallas_available() or _interpret()


def _flash_block_body(has_mask, sm_scale, *refs):
    # m/l ride as [1, T, 1] blocks: compiled Mosaic requires the last
    # two block dims to be (divisible by 8, divisible by 128) or equal
    # to the array dims — a [1, T] block of an [H, T] array is neither
    if has_mask:
        q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, mask_ref, \
            mo_ref, lo_ref, oo_ref = refs
    else:
        q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, \
            mo_ref, lo_ref, oo_ref = refs
    dot = lambda a, b, dims: jax.lax.dot_general(  # noqa: E731
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)
    s = dot(q_ref[0], k_ref[0], ((1,), (1,))) * sm_scale     # [T, S] f32
    if has_mask:
        s = jnp.where(mask_ref[:] != 0, NEG_INF, s)
    m_old = m_ref[0][:, 0]                                    # [T]
    m_new = jnp.maximum(m_old, s.max(axis=-1))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, None])
    mo_ref[0] = m_new[:, None]
    lo_ref[0] = (l_ref[0][:, 0] * alpha + p.sum(axis=-1))[:, None]
    oo_ref[0] = o_ref[0] * alpha[:, None] + \
        dot(p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)))


def _flash_bwd_body(has_mask, sm_scale, *refs):
    """Fused recompute-style backward of one online-softmax block step.

    Recomputes s/p from the saved block inputs (the flash-attention
    memory trade), then applies the exact VJP of ``_block_update`` —
    including jax's tie semantics for the two max ops: ``jnp.maximum``
    splits a tie 50/50 (lax ``_balanced_eq``) and ``reduce_max`` divides
    the cotangent equally among tied lanes — so fused gradients are
    bit-for-bit the same math as differentiating the jnp twin. Five MXU
    dots per head (s, dp, dv, dq, dk); everything else is VPU
    elementwise.
    """
    if has_mask:
        (q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, mask_ref,
         cm_ref, cl_ref, co_ref,
         dq_ref, dk_ref, dv_ref, dm_ref, dl_ref, do_ref) = refs
    else:
        (q_ref, k_ref, v_ref, m_ref, l_ref, o_ref,
         cm_ref, cl_ref, co_ref,
         dq_ref, dk_ref, dv_ref, dm_ref, dl_ref, do_ref) = refs
    dot = lambda a, b, dims: jax.lax.dot_general(  # noqa: E731
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)
    q, k, v, o, co = q_ref[0], k_ref[0], v_ref[0], o_ref[0], co_ref[0]
    m, l = m_ref[0][:, 0], l_ref[0][:, 0]
    cm, cl = cm_ref[0][:, 0], cl_ref[0][:, 0]

    # --- recompute the forward's s / m_new / alpha / p ---
    s = dot(q, k, ((1,), (1,))) * sm_scale                   # [T, S] f32
    if has_mask:
        masked = mask_ref[:] != 0
        s = jnp.where(masked, NEG_INF, s)
    t_row = s.max(axis=-1)                                   # [T]
    m_new = jnp.maximum(m, t_row)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])                          # [T, S]

    # --- VJP proper (cotangents cm/cl/co of m_new/l_new/o_new) ---
    # o_new = o*alpha + p_cast@v ; l_new = l*alpha + sum_j p
    do_ref[0] = co * alpha[:, None]
    dl_ref[0] = (cl * alpha)[:, None]
    p_cast = p.astype(v.dtype)          # forward casts p to v's dtype
    dv_ref[0] = dot(p_cast, co, ((0,), (0,))).astype(dv_ref.dtype)
    dalpha = cl * l + (co * o).sum(axis=-1)                  # [T]
    dp = dot(co, v.astype(jnp.float32), ((1,), (1,))) + cl[:, None]
    # alpha = exp(m - m_new); p = exp(s - m_new)
    dm_new = cm - dalpha * alpha - (dp * p).sum(axis=-1)
    ds = dp * p                                              # [T, S]
    # m_new = maximum(m, t_row): balanced tie split
    sel_m = jnp.where(m > t_row, 1.0,
                      jnp.where(m < t_row, 0.0, 0.5))
    dm_ref[0] = (dalpha * alpha + dm_new * sel_m)[:, None]
    # t_row = reduce_max(s): cotangent split equally among tied lanes
    g_t = dm_new * (1.0 - sel_m)
    eq = (s == t_row[:, None]).astype(jnp.float32)
    ds = ds + (g_t / eq.sum(axis=-1))[:, None] * eq
    if has_mask:
        ds = jnp.where(masked, 0.0, ds)
    ds = ds * sm_scale
    # s_raw = q @ k^T (bf16 operands upcast exactly into the f32 dot)
    dq_ref[0] = dot(ds, k.astype(jnp.float32),
                    ((1,), (0,))).astype(dq_ref.dtype)
    dk_ref[0] = dot(ds, q.astype(jnp.float32),
                    ((0,), (0,))).astype(dk_ref.dtype)


def _flash_compiler_params():
    """Mosaic params for both flash block kernels. The backward holds ~5
    [T, S] f32 temporaries (s, p, dp, ds, tie mask) per head — past the
    default 16 MB *scoped* vmem budget on v5e at T = S = 1024 (the
    histogram kernels raise it too, _hist_compiler_params). With the
    64 MB limit below, compiling
    for a described v5e (H = 8, D = 128) shows the limit: T = S = 1024
    is the largest block shown to compile with the fused backward; at
    2048 the backward runs out of VMEM (RESOURCE_EXHAUSTED) while the
    forward still compiles. Longer sequences need smaller blocks (more
    ring steps), not a larger budget. The head grid axis writes
    disjoint per-head blocks (parallel)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=64 * 1024 * 1024,
    )


def _fused_bwd_enabled() -> bool:
    """Backward selection for flash_block: the fused Pallas kernel by
    default; ``RABIT_FLASH_BWD=recompute`` falls back to differentiating
    the jnp twin through XLA (the pre-r4 behavior, kept as the parity
    oracle)."""
    import os
    return os.environ.get("RABIT_FLASH_BWD", "fused") != "recompute"


def flash_block_bwd(q, k, v, m, l, o, mask_i8, sm_scale, cm, cl, co):
    """Fused backward pass: given the block inputs and output cotangents
    (cm, cl, co), return (dq, dk, dv, dm, dl, do). Shapes mirror
    ``flash_block``; mask_i8 is [T, S] int8 or None."""
    from jax.experimental import pallas as pl

    h, t, d = q.shape
    s_len = k.shape[1]
    has_mask = mask_i8 is not None
    head = lambda i: (i, 0, 0)       # noqa: E731
    whole = lambda i: (0, 0)         # noqa: E731
    col = pl.BlockSpec((1, t, 1), head)
    qd = pl.BlockSpec((1, t, d), head)
    kd = pl.BlockSpec((1, s_len, d), head)
    in_specs = [qd, kd, kd, col, col, qd]
    ins = [q, k, v, m[..., None], l[..., None], o]
    if has_mask:
        in_specs.append(pl.BlockSpec((t, s_len), whole))
        ins.append(mask_i8)
    in_specs += [col, col, qd]
    ins += [cm[..., None], cl[..., None], co]
    dq, dk, dv, dm, dl, do = pl.pallas_call(
        functools.partial(_flash_bwd_body, has_mask, sm_scale),
        grid=(h,),
        in_specs=in_specs,
        out_specs=[qd, kd, kd, col, col, qd],
        out_shape=[_out_struct((h, t, d), q.dtype, *ins),
                   _out_struct((h, s_len, d), k.dtype, *ins),
                   _out_struct((h, s_len, d), v.dtype, *ins),
                   _out_struct((h, t, 1), jnp.float32, *ins),
                   _out_struct((h, t, 1), jnp.float32, *ins),
                   _out_struct((h, t, d), jnp.float32, *ins)],
        compiler_params=_flash_compiler_params(),
        interpret=_interpret(),
    )(*ins)

    def match_vma(g, primal):
        # custom_vjp requires each grad's varying-manual-axes to equal
        # its primal's. A primal replicated over an axis (e.g. the ring
        # scan's m0/l0/o0 init constants under a checked shard_map) gets
        # a cotangent varying over it; the broadcast's true transpose is
        # a psum over the extra axes — exactly what differentiating the
        # jnp twin produces automatically.
        want = getattr(jax.typeof(primal), "vma", None) or frozenset()
        have = getattr(jax.typeof(g), "vma", None) or frozenset()
        extra = tuple(sorted(have - set(want)))
        return jax.lax.psum(g, extra) if extra else g

    grads = (dq, dk, dv, dm[..., 0], dl[..., 0], do)
    return tuple(match_vma(g, p)
                 for g, p in zip(grads, (q, k, v, m, l, o)))


def flash_block(q, k, v, m, l, o, mask, sm_scale):
    """Pallas twin of ring_attention's ``_block_update``: same contract
    (q [H,T,D]; k/v [H,S,D]; m/l [H,T] f32; o [H,T,D] f32; mask [T,S]
    bool or None) and same return (m', l', o').

    Differentiable via a recompute-based custom VJP. By default the
    backward is the fused Pallas kernel (``_flash_bwd_body``): it
    recomputes s/p from the saved inputs and applies the exact VJP of
    the block update on the MXU, so the long-context training path's
    backward throughput is the kernel's, not XLA's.
    ``RABIT_FLASH_BWD=recompute`` reverts to differentiating the
    mathematically identical jnp twin (``_block_update``) through XLA —
    kept as the parity oracle the fused kernel is tested against.
    Either way inputs are cheap to save (the live K/V block is already
    resident in the ring scan carry)."""
    from jax.experimental import pallas as pl

    h, t, d = q.shape
    s_len = k.shape[1]
    has_mask = mask is not None
    head = lambda i: (i, 0, 0)       # noqa: E731
    whole = lambda i: (0, 0)         # noqa: E731
    in_specs = [
        pl.BlockSpec((1, t, d), head), pl.BlockSpec((1, s_len, d), head),
        pl.BlockSpec((1, s_len, d), head), pl.BlockSpec((1, t, 1), head),
        pl.BlockSpec((1, t, 1), head), pl.BlockSpec((1, t, d), head),
    ]
    ins = [q, k, v, m, l, o]
    if has_mask:
        in_specs.append(pl.BlockSpec((t, s_len), whole))
        ins.append(mask.astype(jnp.int8))
    raw_call = pl.pallas_call(
        functools.partial(_flash_block_body, has_mask, sm_scale),
        grid=(h,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, t, 1), head),
                   pl.BlockSpec((1, t, 1), head),
                   pl.BlockSpec((1, t, d), head)],
        out_shape=[_out_struct((h, t, 1), jnp.float32, *ins),
                   _out_struct((h, t, 1), jnp.float32, *ins),
                   _out_struct((h, t, d), jnp.float32, *ins)],
        compiler_params=_flash_compiler_params(),
        interpret=_interpret(),
    )

    def call(q, k, v, m, l, o, *rest):
        # m/l ride as [H, T, 1] through the kernel (tiling note above)
        mo, lo, oo = raw_call(q, k, v, m[..., None], l[..., None], o,
                              *rest)
        return mo[..., 0], lo[..., 0], oo

    def _jnp_twin(q, k, v, m, l, o, mask_i8):
        from ..parallel.ring_attention import _block_update
        return _block_update(q, k, v, m, l, o,
                             None if mask_i8 is None else mask_i8 != 0,
                             sm_scale)

    if has_mask:
        @jax.custom_vjp
        def run(q, k, v, m, l, o, mask_i8):
            return call(q, k, v, m, l, o, mask_i8)

        def fwd(q, k, v, m, l, o, mask_i8):
            return run(q, k, v, m, l, o, mask_i8), \
                (q, k, v, m, l, o, mask_i8)

        def bwd(res, ct):
            *prim, mask_i8 = res
            mask_ct = np.zeros(mask_i8.shape, jax.dtypes.float0)
            if _fused_bwd_enabled():
                return (*flash_block_bwd(*prim, mask_i8, sm_scale, *ct),
                        mask_ct)
            _, vjp = jax.vjp(
                lambda *a: _jnp_twin(*a, mask_i8), *prim)
            return (*vjp(ct), mask_ct)
    else:
        @jax.custom_vjp
        def run(q, k, v, m, l, o):
            return call(q, k, v, m, l, o)

        def fwd(*prim):
            return run(*prim), prim

        def bwd(res, ct):
            if _fused_bwd_enabled():
                return flash_block_bwd(*res, None, sm_scale, *ct)
            _, vjp = jax.vjp(lambda *a: _jnp_twin(*a, None), *res)
            return vjp(ct)

    run.defvjp(fwd, bwd)
    return run(*ins)
