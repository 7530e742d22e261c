"""Symbolic analysis: how many batches b does the multiply need? (Paper §IV-A)

Three estimators, all exposed so tests can verify the paper's ordering
``lower_bound <= b_exact <= b_flops``:

  * ``batch_count_lower_bound`` — Eq. (2): information-theoretic floor from
    mem(C) and aggregate memory M.
  * ``batch_count`` — Alg. 3 line 12: b from the *max per-process* unmerged
    nnz (robust to load imbalance; may exceed the lower bound).
  * per-column upper bounds (``nnz_per_col_upper``) used to size static
    capacities for each batch (JAX needs static shapes — the symbolic step is
    exactly the paper's "symbolic-then-numeric" split, it just also fixes
    buffer capacities here).

The distributed version (communication pattern of Alg. 3) lives in
``repro.core.batched``; this module holds the math.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray

#: bytes per nonzero. Paper uses r=24 (two i64 indices + f64 value); our
#: TPU-native default is r=12 (two i32 local indices + f32 value). The
#: constant is a parameter everywhere it matters.
R_BYTES_PAPER = 24
R_BYTES_DEFAULT = 12

#: r-byte records the compiled ESC step holds per expansion slot
#: (``flops_cap``): the expanded (row, col, val) triple, the sort's packed
#: key and permutation, and the compressed D tile and merged C piece it
#: returns. The v5e ahead-of-time compile of the n = 2^20 protein-network
#: step (``compiled.memory_analysis()``) holds 32-36 B of temporaries plus
#: 12 B of output per slot at r = 12: four records.
ESC_SLOT_RECORDS = 4

#: bytes per element of the dense path's f32 batch buffers
DENSE_VAL_BYTES = 4


def esc_step_bytes(flops_cap: float, r: int = R_BYTES_DEFAULT) -> int:
    """Bytes the ESC fused step holds for ``flops_cap`` expansion slots."""
    return int(math.ceil(ESC_SLOT_RECORDS * r * flops_cap))


def dense_step_bytes(width: int, a_gather_cap: int, k_dim: int, tm: int) -> int:
    """Bytes the dense-accumulator step holds for a batch ``width`` columns
    wide: one gathered row of B and its product per gathered A entry
    (``a_gather_cap`` × width, twice), the densified B block (``k_dim`` ×
    width) and the D accumulator plus its reduce-scattered C (``tm`` ×
    width, twice)."""
    return DENSE_VAL_BYTES * width * (2 * a_gather_cap + k_dim + 2 * tm)


@dataclasses.dataclass(frozen=True)
class SymbolicCounts:
    """Host-side output of the symbolic pass (all numpy).

    Only count *vectors* ever travel (§IV-A, Fig. 8) — the same payload now
    also carries what the numeric pass needs to size selection buffers, so
    no extra communication round is spent on it.
    ``mask_colcounts`` (masked multiplies only) holds the mask's exact
    per-(tile, local column) entry counts — the §V-B observation that a
    strict mask bounds C's structure, so the batch plan can budget survivors
    instead of the full product.

    Two producers, one consumer (``batched.plan_from_symbolic``): the
    distributed pass (``batched.symbolic3d_counts``, counts computed ON the
    grid the operands live on) and the no-device oracle below
    (``host_symbolic_counts``, counts computed from host COO for ANY
    candidate grid shape — the autotuner's way of pricing grids the
    operands were never scattered to).
    """

    percol: np.ndarray  # (pr, pc, l, tn_b) flops per local output column
    b_colcounts: np.ndarray  # (pr, pc, l, tn_b) B entries per local column
    mask_colcounts: np.ndarray = None  # (pr, pc, l, wl) mask nnz, or None


def _host_triplets(a):
    """(rows, cols) of the live entries of a host COO (duck-typed: anything
    with ``rows``/``cols``/``nnz`` numpy-convertible attributes works)."""
    nnz = int(a.nnz)
    return (
        np.asarray(a.rows[:nnz]).astype(np.int64),
        np.asarray(a.cols[:nnz]).astype(np.int64),
    )


def host_tile_counts(a, grid_shape, kind: str) -> np.ndarray:
    """Per-tile nnz of ``a`` laid out as ``kind`` on a CANDIDATE grid shape
    — pure host math (mirrors ``distsparse._tile_layout``'s indexing without
    building tiles or touching a device). Returns (pr, pc, l)."""
    pr, pc, l = grid_shape
    m, n = a.shape
    rows, cols = _host_triplets(a)
    if kind in ("A", "C"):
        assert m % pr == 0 and n % (pc * l) == 0, (a.shape, grid_shape)
        w, wl = n // pc, n // pc // l
        ti = rows // (m // pr)
        tj = cols // w
        tk = (cols % w) // wl
    else:
        assert m % (pr * l) == 0 and n % pc == 0, (a.shape, grid_shape)
        w, wl = m // pr, m // pr // l
        ti = rows // w
        tk = (rows % w) // wl
        tj = cols // (n // pc)
    tile_id = (ti * pc + tj) * l + tk
    return np.bincount(tile_id, minlength=pr * pc * l).reshape(pr, pc, l)


def host_symbolic_counts(a, b, grid_shape, mask=None) -> SymbolicCounts:
    """The symbolic pass as a host ORACLE: exact per-column flops / count
    vectors for ``a``·``b`` distributed on a candidate ``grid_shape`` —
    without scattering anything or touching a device.

    Reproduces ``batched._symbolic3d_jit`` bit-for-bit (asserted by tests):
    A's per-(row block, layer, stage-k) counts contracted against B's
    entries through the stage coordinate k_idx = s·wl + local row. Layer
    grids must be square (pr == pc) OR single-layer (l == 1): with one
    layer the stage coordinate equals the global contraction index on both
    sides, so rectangular pr×pc×1 grids align; with l > 1 the per-layer
    slicing only lines up when pr == pc. This is what lets the autotuner
    enumerate (pr, pc, l) candidates from one pass over the host COO per
    candidate, no trial multiplies.
    """
    pr, pc, l = grid_shape
    assert pr == pc or l == 1, \
        f"square layer grids or l == 1 only, got {grid_shape}"
    m_a, k_dim = a.shape
    k_dim_b, n_b = b.shape
    assert k_dim == k_dim_b, (a.shape, b.shape)
    w_a, wl_a = k_dim // pc, k_dim // pc // l
    assert m_a % pr == 0 and k_dim % (pc * l) == 0, (a.shape, grid_shape)
    assert k_dim % (pr * l) == 0 and n_b % pc == 0, (b.shape, grid_shape)
    tn_b = n_b // pc
    k_tot = pc * wl_a

    # A: per-(row block, layer, stage coordinate) column counts — the host
    # image of cc_full = all_gather(col_counts, COL_AX) per (i, k)
    ar, ac = _host_triplets(a)
    a_i = ar // (m_a // pr)
    a_k = (ac % w_a) // wl_a
    a_q = (ac // w_a) * wl_a + (ac % wl_a)
    acc = np.zeros((pr, l, k_tot), np.int64)
    np.add.at(acc, (a_i, a_k, a_q), 1)

    # B: tile coordinates + stage coordinate k_idx = s*wl + local row
    br, bc = _host_triplets(b)
    w_b, wl_b = k_dim // pr, k_dim // pr // l
    b_s = br // w_b
    b_k = (br % w_b) // wl_b
    b_lr = br % wl_b
    b_j = bc // tn_b
    b_lc = bc % tn_b
    b_q = b_s * wl_b + b_lr

    bcc = np.zeros((pr, pc, l, tn_b), np.int64)
    np.add.at(bcc, (b_s, b_j, b_k, b_lc), 1)

    # percol[i, j, k, c] = Σ over B entries of (grid col j, layer k, local
    # col c): A's stage-k_idx count in row block i — vectorized as one
    # weighted bincount per row block over the (j, k, c) key
    key = (b_j * l + b_k) * tn_b + b_lc
    percol = np.zeros((pr, pc * l * tn_b), np.int64)
    for i in range(pr):
        percol[i] = np.round(np.bincount(
            key, weights=acc[i, b_k, b_q], minlength=pc * l * tn_b
        )).astype(np.int64)
    percol = percol.reshape(pr, pc, l, tn_b)

    mcc = None
    if mask is not None:
        assert mask.shape == (m_a, n_b), (mask.shape, a.shape, b.shape)
        w_c, wl_c = n_b // pc, n_b // pc // l
        mr, mc_ = _host_triplets(mask)
        mcc = np.zeros((pr, pc, l, wl_c), np.int64)
        np.add.at(mcc, (
            mr // (m_a // pr), mc_ // w_c, (mc_ % w_c) // wl_c, mc_ % wl_c,
        ), 1)

    return SymbolicCounts(
        percol=percol, b_colcounts=bcc,
        mask_colcounts=mcc,
    )


@dataclasses.dataclass(frozen=True)
class SymbolicResult:
    """Host-side outcome of the symbolic step (all python ints)."""

    num_batches: int
    max_unmerged_nnz: int  # max over processes of unmerged output nnz (b=1)
    max_nnz_a: int
    max_nnz_b: int
    flops: int  # total multiply count (2*flops = FLOPs)
    lower_bound: int  # Eq. (2)

    def per_batch_capacity(self, slack: float = 1.25) -> int:
        """Static per-process unmerged capacity to allocate for one batch."""
        cap = int(math.ceil(self.max_unmerged_nnz / max(self.num_batches, 1) * slack))
        return max(cap, 8)


def batch_count_lower_bound(
    mem_c_bytes: int, total_memory: int, nnz_a: int, nnz_b: int, r: int = R_BYTES_DEFAULT
) -> int:
    """Paper Eq. (2): b >= ceil(mem(C) / (M - r(nnz(A)+nnz(B))))."""
    denom = total_memory - r * (nnz_a + nnz_b)
    if denom <= 0:
        raise MemoryError(
            f"inputs alone ({r * (nnz_a + nnz_b)}B) exceed aggregate memory "
            f"({total_memory}B) — paper precondition M > r(nnz(A)+nnz(B)) violated"
        )
    return max(1, math.ceil(mem_c_bytes / denom))


def batch_count(
    max_unmerged_nnz: int,
    max_nnz_a: int,
    max_nnz_b: int,
    per_process_memory: int,
    r: int = R_BYTES_DEFAULT,
) -> int:
    """Paper Alg. 3 line 12: b = ceil(r*maxnnzC / (M/p - r(maxnnzA+maxnnzB))).

    Uses per-process *maxima* so no process exhausts memory under load
    imbalance (§IV-A: "robust to different sparsity patterns").
    """
    denom = per_process_memory - r * (max_nnz_a + max_nnz_b)
    if denom <= 0:
        raise MemoryError(
            f"per-process inputs ({r * (max_nnz_a + max_nnz_b)}B) exceed "
            f"per-process memory ({per_process_memory}B)"
        )
    return max(1, math.ceil(r * max_unmerged_nnz / denom))


def batching_plan_columns(n: int, num_batches: int, num_layers: int) -> int:
    """Round b up so the block-cyclic split divides the column dimension.

    Returns the adjusted batch count. Paper Fig. 1(i): each batch is l blocks
    of width n/(b*l); we need (b*l) | n.
    """
    b = num_batches
    b_max = n // num_layers  # finest split: one block-cyclic block per batch
    if b > b_max:
        raise MemoryError(
            f"need {num_batches} batches but only {b_max} column batches exist "
            f"({n} cols / {num_layers} layers) — aggregate memory insufficient "
            f"even at the finest batching granularity (paper precondition)"
        )
    while n % (b * num_layers) != 0:
        b += 1
        if b > b_max:
            raise MemoryError(
                f"cannot split {n} columns into >= {num_batches} batches with "
                f"{num_layers} layers"
            )
    return b


def fold_block_cyclic(
    percol: np.ndarray, num_batches: int, num_layers: int
) -> np.ndarray:
    """Fold per-local-column vectors (..., n) into per-(batch, piece) sums.

    The block-cyclic split (paper Fig. 1(i)) divides n local columns into
    ``num_batches * num_layers`` blocks of width w = n/(b·l); block t belongs
    to batch ``t % b`` and fiber piece ``t // b``. Returns an array of shape
    (..., num_batches, num_layers) — the host-side math behind both the
    per-batch flops capacities and the exact per-batch selection counts.
    """
    *lead, n = percol.shape
    w = n // (num_batches * num_layers)
    assert w * num_batches * num_layers == n, (n, num_batches, num_layers)
    blocks = percol.reshape(*lead, num_layers, num_batches, w).sum(axis=-1)
    return np.swapaxes(blocks, -1, -2)  # (..., batch, piece)


def rup8(x: int) -> int:
    """Round up to a multiple of 8 (static-capacity alignment)."""
    return ((x + 7) // 8) * 8


def rup_pow2(x: int) -> int:
    """Round up to the next power of two.

    Capacity quantization for iterated multiplies (MCL, §V-C): per-iteration
    nnz drift would otherwise produce a fresh ``BatchCaps`` — and a fresh
    compile of the fused SPMD step — every iteration. Pow2 buckets collapse
    nearby capacity plans onto one static signature so the jit cache hits.
    """
    return 1 << max(int(x) - 1, 0).bit_length()


# Open-addressing slot of the hash-accumulator multiply: i32 key + f32 value.
HASH_SLOT_BYTES = 8

# Default table occupancy target (slots per merged output entry). 1/1.75 ≈
# 0.57 occupancy keeps expected linear-probe chains short while the table
# stays within ~2 slots of footprint per survivor.
HASH_LOAD_FACTOR = 1.75


def estimate_mem_c_bytes(
    flops: int, compression_factor: float, r: int,
    local_path: str = "esc", load_factor: float = None,
) -> int:
    """mem(C) of one multiply's resident intermediate.

    ESC path: r * Σ_k nnz(D^k) — bounded by r*flops (no merging, worst case)
    and approximated by r*flops/cf_layer when layer-level merging is counted.

    Hash path (``local_path="hash"``): the resident structure is the
    open-addressing table over the *merged* output, so the footprint is
    slot_bytes · load_factor · (flops/cf) — the measured load factor scales
    the table, not the COO entry size, which is why high-cf multiplies fit
    where the ESC expansion doesn't.
    """
    nnz = flops / max(compression_factor, 1.0)
    if local_path == "hash":
        lf = HASH_LOAD_FACTOR if load_factor is None else load_factor
        return int(math.ceil(nnz * lf * HASH_SLOT_BYTES))
    return int(r * nnz)
