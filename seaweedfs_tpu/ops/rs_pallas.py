"""Fused Pallas TPU kernel for the GF(2^8) bit-plane matmul codec.

The pure-XLA path (ops/rs_jax.py) materializes the bit-planes tensor
([8k, B], 8x the data bytes) in HBM between the unpack and the matmul, so it
is HBM-bound at roughly 1/20th of peak.  This kernel fuses
unpack -> MXU matmul -> mod2 -> pack inside VMEM, so HBM traffic is just
data-in (k*B) + parity-out (m*B) and the codec is bound by the MXU, not by
HBM.  The data rides shard-major ([KI, V, B], see gf_matmul_bits_pallas_sm):
dense on the tiled axes and already in the order .ecNN files are written.

Layout trick: planes are *bit-index-major* ("plane-major"): row j*K + c of the
plane tensor is bit j of shard-row c.  Unpacking that order is a pure
sublane-concat (no transpose in Mosaic):

    planes = ((d[None] >> shifts[:, None, None]) & 1).reshape(8K, TB)

and packing the output back is a reshape + weighted sum over the leading
axis.  The generator bit-matrix is permuted to match on the host
(rs_matrix_planemajor), once, at trace time.

One kernel serves encode *and* reconstruct — both are just
out[MO, B] = Mbits[8MO, 8KI] ∘GF2∘ in[KI, B] with a different matrix.

The clay codec additionally gets FULLY fused kernels (encode and
single-loss repair): the companion-pair uncouple, the [m, k0] layer-MDS
matmul and the couple stage run per batch tile entirely in VMEM, so the
uncoupled operand never round-trips HBM and the shortened construction's
virtual zero rows are synthesized in registers instead of being
materialized or streamed (see _clay_fused_encode_kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gf256

LANE = 128


def plane_major_perm(mo: int, ki: int) -> tuple[np.ndarray, np.ndarray]:
    """Static (rows, cols) index arrays that permute a shard-major bit
    matrix [8MO, 8KI] into plane-major order: new row i*MO + r <- old row
    r*8 + i, new col j*KI + c <- old col c*8 + j.  Usable host-side (numpy
    fancy indexing) or on-device (static gather inside jit/shard_map)."""
    i = np.arange(8 * mo) // mo
    r = np.arange(8 * mo) % mo
    rows = r * 8 + i
    j = np.arange(8 * ki) // ki
    c = np.arange(8 * ki) % ki
    cols = c * 8 + j
    return rows, cols


def to_plane_major(bitmat: np.ndarray, mo: int, ki: int) -> np.ndarray:
    """Permute rs_matrix.bit_matrix output (shard-major, [8MO, 8KI]) into
    plane-major order (see plane_major_perm)."""
    assert bitmat.shape == (8 * mo, 8 * ki)
    rows, cols = plane_major_perm(mo, ki)
    return np.ascontiguousarray(bitmat[rows][:, cols])


SHARD_MAJOR_VBLOCK = 8  # volumes per grid step in the shard-major kernel


def _gf2_matmul_kernel_sm(mbits_ref, data_ref, out_ref, *, ki: int,
                          mo: int):
    """Shard-major block: out[MO, VB, TB] = Mbits ∘GF2∘ data[KI, VB, TB].

    VB volumes ride the sublane axis; the matmul contracts the 8*KI planes
    with (VB, TB) flattened onto the lanes.  The dot runs in the matrix's
    dtype — int8 doubles MXU throughput vs bf16 on v5e and is exact here
    (operands 0/1, partial sums <= 8K <= 2040 in the int32 accumulator)."""
    d = data_ref[...].astype(jnp.int32)  # [KI, VB, TB]
    _, vb, tb = d.shape
    dot_dtype = mbits_ref.dtype
    acc_dtype = jnp.int32 if dot_dtype == jnp.int8 else jnp.float32
    in_shifts = jax.lax.broadcasted_iota(jnp.int32, (8, ki, vb, tb), 0)
    planes = (jnp.broadcast_to(d[None], (8, ki, vb, tb)) >> in_shifts) & 1
    planes = planes.reshape(8 * ki, vb * tb).astype(dot_dtype)
    acc = jnp.dot(mbits_ref[...], planes,
                  preferred_element_type=acc_dtype)  # [8*MO, VB*TB]
    bits = acc.astype(jnp.int32) & 1
    v = bits.reshape(8, mo, vb, tb)
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (8, mo, vb, tb), 0)
    out_ref[...] = jnp.sum(v << out_shifts, axis=0).astype(jnp.uint8)


SM_DEFAULT_BLOCK_B = 512  # swept best on v5e (retired setup; int8)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "interpret"))
def gf_matmul_bits_pallas_sm(mbits_pm: jax.Array, data: jax.Array, *,
                             block_b: int = SM_DEFAULT_BLOCK_B,
                             interpret: bool = False) -> jax.Array:
    """Shard-major layout: data [KI, V, B] -> parity [MO, V, B].

    The [V, K, B] layout pads K=10 up to the sublane tile of 16 — a 1.6x
    HBM expansion on the dominant operand (and the OOM/copy the compiler
    inserts to produce it).  Shard-major puts (V, B) on the tiled axes:
    dense rows, no padding, and each shard's bytes for ALL volumes are
    contiguous — which is also the natural layout for writing .ecNN files.
    V must be a multiple of 8 (pad with zero volumes).
    """
    ki, v, b = data.shape
    mo = mbits_pm.shape[0] // 8
    assert mbits_pm.shape == (8 * mo, 8 * ki)
    assert v % SHARD_MAJOR_VBLOCK == 0, f"V={v} must be a multiple of 8"
    assert b % block_b == 0, f"B={b} must be a multiple of {block_b}"
    grid = (v // SHARD_MAJOR_VBLOCK, b // block_b)
    return pl.pallas_call(
        functools.partial(_gf2_matmul_kernel_sm, ki=ki, mo=mo),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * mo, 8 * ki), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ki, SHARD_MAJOR_VBLOCK, block_b),
                         lambda i, j: (0, i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((mo, SHARD_MAJOR_VBLOCK, block_b),
                               lambda i, j: (0, i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mo, v, b), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(mbits_pm, data)


def _block_vmem_bytes(ki: int, mo: int, lanes: int) -> int:
    """VMEM bytes one grid step of the shard-major kernel keeps live for a
    flattened lane count of `lanes` (VB*TB): the double-buffered u8
    operand and output blocks, the int32 unpack of the operand, the int8
    bit-planes and the int32 accumulator.  A budget model, not an exact
    allocator trace — it only has to scale right in ki and mo."""
    return (2 * ki * lanes        # u8 operand block, double-buffered
            + 4 * ki * lanes      # int32 unpack
            + 8 * ki * lanes      # int8 planes [8*ki, lanes]
            + 32 * mo * lanes     # int32 accumulator [8*mo, lanes]
            + 2 * mo * lanes)     # u8 out block, double-buffered


def sm_block_b_for(ki: int, mo: int) -> int:
    """Geometry-aware block_b for the shard-major kernel.

    ki <= 16 keeps the swept 512 (the v5e optimum across RS(10,4)..
    RS(16,8) on a retired setup; not yet re-swept on this machine's
    chip) — at 8*ki <= 128 the contraction dim
    fills at most one MXU pass and the sweep already covered the range.
    Wider stripes (RS(28,4) class) grow every per-block tensor linearly
    in ki, so the same block_b crowds the double-buffered operands out
    of VMEM; halve the tile until the working set is back under the
    swept envelope (floor 128 so a block still spans a full lane tile)."""
    if ki <= 16:
        return SM_DEFAULT_BLOCK_B
    budget = _block_vmem_bytes(16, 8, SHARD_MAJOR_VBLOCK * SM_DEFAULT_BLOCK_B)
    b = SM_DEFAULT_BLOCK_B
    while b > 128 and _block_vmem_bytes(ki, mo, SHARD_MAJOR_VBLOCK * b) > budget:
        b //= 2
    return b


def to_sm_layout(arr: np.ndarray) -> np.ndarray:
    """HOST-side relayout [.., S, B] -> shard-major [S, 8*prod(lead), B/8].

    TPU tiles the last two dims of a u8 array in (32, 128) blocks, so a
    [10, B] operand pads 10 -> 16 sublanes (1.6x HBM expansion) and any
    DEVICE-side reshape to fix it is a real HBM copy (XLA materializes the
    retiling).  Splitting each row's byte axis into 8 sublane rows host-side
    is a free numpy view for 2D input (one memcpy for a leading batch), and
    [S, 8V, B/8] is dense on the tiled axes — the layout
    gf_matmul_bits_pallas_sm consumes at full speed."""
    *lead, s, b = arr.shape
    assert b % 8 == 0, f"B={b} must be a multiple of 8"
    v = int(np.prod(lead)) if lead else 1
    if lead:
        arr = np.ascontiguousarray(np.moveaxis(arr, -2, 0))
    return arr.reshape(s, 8 * v, b // 8)


def from_sm_layout(out: np.ndarray, lead: tuple, b: int) -> np.ndarray:
    """Inverse of to_sm_layout for the kernel output [MO, 8V, B/8]."""
    mo = out.shape[0]
    if not lead:
        return out.reshape(mo, b)
    flat = out.reshape(mo, *lead, b)
    return np.ascontiguousarray(np.moveaxis(flat, 0, -2))


# -- fused clay kernels -----------------------------------------------------
#
# The clay encode is three steps: uncouple, the [m, k0] layer-MDS matmul,
# couple.  Run as separate device ops, the uncoupled operand (k0 rows,
# including the virtual zero rows of the shortened construction) and the
# uncoupled parity each make a round trip through HBM.  These kernels do
# all three steps per batch tile in VMEM, so HBM sees data in and parity
# out only — (k+m)/k bytes per data byte — and the zero rows exist only as
# register zeros.  The single-loss repair kernel does the same for the
# uncouple, the [q, k0] row solve and the out-of-plane back-substitution.
#
# Everything clay-specific (grid geometry q x t, coupling constants) comes
# in as static kwargs so this module stays free of clay imports; the
# companion permutation is the same digit-axis swapaxes the XLA path uses
# (clay_structured._pair_swap), which keeps the two paths bit-identical
# by construction.

CLAY_FUSED_CB = 128   # minimum column tile (one u8 lane tile)

# Mosaic's default scoped-VMEM limit on v5e is 16 MiB of the chip's 128
# MiB.  The fused clay kernels hold a whole [alpha, cb] column tile per
# operand row, so alpha = 512 geometries (clay (16,8): 35 MiB encode,
# 18 MiB repair by the v5e compiler's own count) are refused at the
# default even at the 128-lane floor of cb.  One raised limit for every
# geometry; tests/test_tpu_compile.py compiles (10,4) and (16,8) under it.
CLAY_FUSED_VMEM_LIMIT = 64 << 20


def clay_fused_cb_for(rows: int, w_a: int) -> int:
    """Column-tile width for the fused clay kernels: grow cb while the
    flattened matmul width rows*cb stays ~32K lanes (the in-VMEM planes
    tensor stays ~3MB at alpha = 256 int8) and cb divides the window's
    w_a — small-alpha test geometries then still amortize grid overhead
    instead of running 128-lane slivers."""
    cb = CLAY_FUSED_CB
    while cb * 2 <= w_a and w_a % (cb * 2) == 0 and rows * cb * 2 <= 32768:
        cb *= 2
    return cb


def _gf_const_mul_i32(const: int, x):
    """y = const ∘GF∘ x elementwise for int32 byte values (0..255):
    const·x = XOR over set bits j of x of the byte const·2^j — eight
    select-xors on the VPU, the in-kernel form of
    clay_structured._gf_const_mul."""
    y = jnp.zeros_like(x)
    for j in range(8):
        term = int(gf256.mul(np.uint8(const), np.uint8(1 << j)))
        y = y ^ (((x >> j) & 1) * jnp.int32(term))
    return y


def _gf2_planes_matmul(mbits_ref, u, rows: int, mo: int):
    """Shared tail of the fused clay kernels: u [rows, N] int32 bytes ->
    out [mo, N] int32 bytes through the plane-major GF(2^8) bit-plane
    matmul (same math as _gf2_matmul_kernel_sm, operand already in
    registers)."""
    n = u.shape[-1]
    in_shifts = jax.lax.broadcasted_iota(jnp.int32, (8, rows, n), 0)
    planes = ((jnp.broadcast_to(u[None], (8, rows, n)) >> in_shifts) & 1) \
        .reshape(8 * rows, n).astype(mbits_ref.dtype)
    acc = jnp.dot(mbits_ref[...], planes,
                  preferred_element_type=jnp.int32)   # [8*mo, N]
    v = (acc & 1).reshape(8, mo, n)
    out_shifts = jax.lax.broadcasted_iota(jnp.int32, (8, mo, n), 0)
    return jnp.sum(v << out_shifts, axis=0)


def _clay_fused_encode_kernel(rbits_ref, data_ref, out_ref, *, k: int,
                              q: int, t: int, gamma: int, det_inv: int):
    """One (window, column-tile) block of the fused clay encode:
    data [k, 1, alpha, cb] -> parity [m=q, 1, alpha, cb], uncouple +
    layer-MDS + couple without leaving VMEM.

    Virtual zero nodes (ids k..k0-1 of the shortened construction) are
    synthesized per grid row as register zeros — with minimal t only ONE
    row is partial (k > q*(t-2)), so the zeros never touch HBM and never
    widen the streamed operand."""
    alpha = q ** t
    d = data_ref[:, 0].astype(jnp.int32)          # [k, alpha, cb]
    cb = d.shape[-1]
    mask_shape = (q,) + (q,) * t + (1,)
    xi = jax.lax.broadcasted_iota(jnp.int32, mask_shape, 0)
    u_rows = []
    for y in range(t - 1):
        lo, hi = y * q, (y + 1) * q
        if hi <= k:
            row = d[lo:hi]
        else:   # the one partial grid row: real nodes + virtual zeros
            row = jnp.concatenate(
                [d[lo:k], jnp.zeros((hi - k, alpha, cb), jnp.int32)])
        # [x, z_{t-1}, .., z_0, cb]; companion = swap x with digit z_y
        s = row.reshape(q, *((q,) * t), cb)
        ax = 1 + (t - 1 - y)
        comp = jnp.swapaxes(s, 0, ax)
        zy = jax.lax.broadcasted_iota(jnp.int32, mask_shape, ax)
        u_rows.append(jnp.where(xi == zy, s,
                                s ^ _gf_const_mul_i32(gamma, comp)))
    u = jnp.stack(u_rows).reshape(q * (t - 1), alpha * cb)
    par = _gf2_planes_matmul(rbits_ref, u, q * (t - 1), q)
    # parity row y = t-1: companions pair within the row (digit z_{t-1},
    # axis 1), couple back: C = (U ^ g*U[comp]) / (1 + g^2)
    p = par.reshape(q, *((q,) * t), cb)
    comp = jnp.swapaxes(p, 0, 1)
    zy = jax.lax.broadcasted_iota(jnp.int32, mask_shape, 1)
    cpl = jnp.where(xi == zy, p, _gf_const_mul_i32(
        det_inv, p ^ _gf_const_mul_i32(gamma, comp)))
    out_ref[:, 0] = cpl.reshape(q, alpha, cb).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=(
    "q", "t", "gamma", "det_inv", "cb", "interpret"))
def clay_fused_encode_pallas(rbits_pm: jax.Array, data4: jax.Array, *,
                             q: int, t: int, gamma: int, det_inv: int,
                             cb: int = CLAY_FUSED_CB,
                             interpret: bool = False) -> jax.Array:
    """Fused clay encode: data4 [k, n_win, alpha, w_a] uint8 (the free
    host view of the natural [k, W] slab) -> parity [m, n_win, alpha,
    w_a].  rbits_pm is the layer-MDS solve matrix R = gen[k0:] in
    plane-major bit form ([8m, 8k0] int8, see to_plane_major)."""
    k, n_win, alpha, w_a = data4.shape
    k0 = q * (t - 1)
    assert alpha == q ** t, (alpha, q, t)
    assert rbits_pm.shape == (8 * q, 8 * k0), rbits_pm.shape
    assert w_a % cb == 0 and cb % LANE == 0, (w_a, cb)
    grid = (n_win, w_a // cb)
    return pl.pallas_call(
        functools.partial(_clay_fused_encode_kernel, k=k, q=q, t=t,
                          gamma=gamma, det_inv=det_inv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * q, 8 * k0), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1, alpha, cb), lambda i, j: (0, i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((q, 1, alpha, cb), lambda i, j: (0, i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((q, n_win, alpha, w_a), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=CLAY_FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(rbits_pm, data4)


def _clay_fused_repair_kernel(rbits_ref, x_ref, out_ref, *, k: int, q: int,
                              t: int, lost: int, gamma: int,
                              inv_gamma: int):
    """One (window, column-tile) block of the fused single-loss repair:
    helpers' repair-plane cells [H, 1, beta, cb] -> the lost node's full
    window content [1, alpha, cb], layer-major.

    Per plane layer the unknown U cells are EXACTLY the lost node's grid
    row (the other q-1 row members' companions live on the lost node,
    out of plane), leaving exactly k0 known rows — uncouple them with
    in-plane digit-axis swaps, solve the row with the static [q, k0]
    matrix (clay_structured.repair_parts), then recover the lost node's
    out-of-plane cells from the coupling with its row's helpers:
    C[lost, z'] = (U[helper, z] ^ C[helper, z]) / gamma."""
    m = q
    n0 = q * t
    beta = q ** (t - 1)
    d = x_ref[:, 0].astype(jnp.int32)              # [H, beta, cb]
    cb = d.shape[-1]
    lost_int = lost if lost < k else n0 - m + (lost - k)
    x0, y0 = lost_int % q, lost_int // q

    def ext_of(i: int):
        if i < k:
            return i
        if i >= n0 - m:
            return k + (i - (n0 - m))
        return None          # virtual zero node

    helpers = [e for e in range(k + m) if e != lost]   # ascending ids
    zeros = jnp.zeros((beta, cb), jnp.int32)
    cells = [zeros if ext_of(i) is None or i == lost_int
             else d[helpers.index(ext_of(i))] for i in range(n0)]
    # plane lattice: free digit positions (all y != y0), descending —
    # ascending plane rank is row-major over them
    free = [y for y in range(t - 1, -1, -1) if y != y0]
    fdims = tuple(q for _ in free)
    mask_shape = (q,) + fdims + (1,)
    xi = jax.lax.broadcasted_iota(jnp.int32, mask_shape, 0)
    u_rows = []
    for y in range(t):
        if y == y0:
            continue
        row = jnp.stack(cells[y * q:(y + 1) * q])   # [q, beta, cb]
        s = row.reshape(q, *fdims, cb)
        ax = 1 + free.index(y)
        comp = jnp.swapaxes(s, 0, ax)
        zy = jax.lax.broadcasted_iota(jnp.int32, mask_shape, ax)
        u_rows.append(jnp.where(xi == zy, s,
                                s ^ _gf_const_mul_i32(gamma, comp)))
    k0 = n0 - m
    u = jnp.stack(u_rows).reshape(k0, beta * cb)
    u_y0 = _gf2_planes_matmul(rbits_ref, u, k0, q).reshape(q, *fdims, cb)
    # x = x0 is the lost node's in-plane (diagonal) cell: C = U; other x
    # recover the out-of-plane cell z' = z with digit y0 := x
    c_row = jnp.stack([zeros if x == x0 else cells[y0 * q + x]
                       for x in range(q)]).reshape(q, *fdims, cb)
    vals = jnp.where(xi == x0, u_y0,
                     _gf_const_mul_i32(inv_gamma, u_y0 ^ c_row))
    # vals axes [digit z_{y0}, free digits desc, cb] -> natural
    # [z_{t-1}, .., z_0, cb] layer order
    out = jnp.moveaxis(vals, 0, t - 1 - y0)
    out_ref[0] = out.reshape(q ** t, cb).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=(
    "k", "q", "t", "lost", "gamma", "inv_gamma", "cb", "interpret"))
def clay_fused_repair_pallas(rbits_pm: jax.Array, x4: jax.Array, *,
                             k: int, q: int, t: int, lost: int,
                             gamma: int, inv_gamma: int,
                             cb: "int | None" = None,
                             interpret: bool = False) -> jax.Array:
    """Fused single-loss clay repair: x4 [H, n_win, beta, w_a] uint8 —
    helper-major (external ids ascending, lost excluded), plane layers
    ascending — -> the lost shard's windows [n_win, alpha, w_a] in the
    natural layer-major layout.  rbits_pm is repair_parts' [q, k0] row
    solve matrix in plane-major bit form."""
    h, n_win, beta, w_a = x4.shape
    m = q
    k0 = q * t - m
    alpha = beta * q
    assert h == k + m - 1, (h, k, m)
    assert beta == q ** (t - 1), (beta, q, t)
    assert rbits_pm.shape == (8 * q, 8 * k0), rbits_pm.shape
    if cb is None:
        cb = clay_fused_cb_for(beta, w_a)
    assert w_a % cb == 0 and cb % LANE == 0, (w_a, cb)
    grid = (n_win, w_a // cb)
    return pl.pallas_call(
        functools.partial(_clay_fused_repair_kernel, k=k, q=q, t=t,
                          lost=lost, gamma=gamma, inv_gamma=inv_gamma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * q, 8 * k0), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h, 1, beta, cb), lambda i, j: (0, i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, alpha, cb), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_win, alpha, w_a), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=CLAY_FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(rbits_pm, x4)
