"""Pallas TPU flash-attention kernel — the fused hot path behind
`ops.attention.full_attention` and the ring-attention block compute.

Net-new relative to the reference (william-wang/elasticdl has no attention
anywhere — SURVEY §5 long-context), but central to the rebuild's transformer
path: the XLA fallback materializes the (B, H, Tq, Tk) score matrix in HBM,
which caps sequence length and burns HBM bandwidth; this kernel streams KV
blocks through VMEM with the online-softmax recurrence so scores never leave
the chip's vector memory, and the backward recomputes them blockwise
(flash-attention style) instead of saving them.

Forward and backward are ONE kernel each (`flash_attention_fwd`,
`_fwd_resident_kernel`; `flash_attention_bwd`, `_bwd_kernel`) wherever a
key-value head fits the chip's VMEM whole (`fwd_route`, `bwd_route`:
`resident`). Their grids are (B, key-value heads, the group's query heads x q
blocks), the last axis sequential; the head's whole k and v (fetched once a
head: a group's query heads repeat the block index and fetch nothing) stay in
VMEM across that axis, and in the backward float32 accumulators of its whole
dk and dv too. A step takes one q block and loops over the kv blocks it sees —
0..i under the causal mask, the band under a window, from the offsets in SMEM
(`_visible_kv`) — slicing k and v out of the resident block. The whole blocks
and the masked edge blocks (the diagonal's; under a window also the band's
first) are separate loops with straight-line bodies (`_for_visible_kv`), kv
blocks ascending: no grid step, no fetch and no `pl.when` for a pair above the
diagonal or outside the band, and no element mask where every pair is visible.

The forward's step runs the online-softmax recurrence (`_softmax_step`: two
matmuls a pair). Where the head does not fit — ring attention's long keys —
the `streaming` forward (`_fwd_kernel`) takes a grid step and a K/V fetch for
every (q block, kv block) pair and skips the dead ones by `pl.when`; both
kernels run the SAME step on the same blocks in the same order, so the two
routes agree to the bit, output and logsumexp, on the CPU and on a v5e
(PERF.md section 6, PR 46). The forward holds no dk and dv, so every shape
that takes the resident backward takes the resident forward (a bfloat16 head
of 128 up to 65 536 keys on a v5e's 128 MiB).

The backward's step computes s = q·kT, p = exp(s − lse), dp = do·vT and ds
ONCE for the pair and feeds dv += pT·do, dk += dsT·q and the step's dq
+= ds·k. Five matmuls a pair, where the `split` route's two kernels
(`flash_attention_bwd_dq`, `_bwd_dkv`: each streams kv blocks through Mosaic's
default 16 MB and each recomputes s and dp) run seven, a second exp and a
second fetch of q, k, v, do, o. The operands' dtypes and the order of every
sum are the split route's — dq over kv blocks ascending, dk and dv over the
group's heads, then q blocks — so the two routes agree to the bit, on the CPU
and on a v5e (PERF.md section 6, PR 37).

What the resident backward holds for a head is its whole k and v, the dk and
dv blocks it writes and their float32 accumulators, and the pipeline gives
every blocked operand TWO buffers — also the four whole-head blocks, whose
index changes once a head, so that the second buffer buys the overlap of one
fetch and one write-back a head (8 MB each way at 32 768 keys: ≈ 10 µs each)
and costs as much VMEM as the first. With two, a bfloat16 head of 128 — or of
64, which takes a whole lane tile (`_in_vmem`) — fits a v5e up to 16 384 keys,
a head of 256 up to 8192. Where two do not fit, the plan prices the same
kernel with ONE buffer for each of the four (`pl.Buffered(1)` on their
BlockSpecs; `Plan.buffers`), which takes it to 32 768 keys of 128 or 64 and
16 384 of 256 (PERF.md section 6, PR 63: 7.5 µs a (q block, kv block) pair at
32 768 keys of 64 where the split route takes 13, dq, dk and dv the same in
every element); 65 536 keys of 128 fit neither way and take the split route. A
shape that fits with two is planned, traced and compiled as it was before one
was an answer.

`fwd_route` and `bwd_route` decide from the keys, the head, the dtype, whether
the call has a data mask and the chip's VMEM alone and log their answer — the
route, the buffers, the bytes — once a shape. The forward's kernels and the
resident backward pass `vmem_limit_bytes` (three quarters of the chip's); the
split kernels pass none. The forward's and
the backward's blocks are planned apart (`_plan_blocks`): the residuals are q,
k, v, out and the logsumexp whatever blocks made them.

Layout: the public contract is (B, T, H, D) like `full_attention`; the
kernel internally works on (B, H, T, D) because Mosaic requires the last two
block dims to be (8·k, 128·k)-tiled or full — a per-head (…, 1, D) block in
the (B, T, H, D) layout violates that. The only residual saved is the
logsumexp, lane-broadcast to (B, H, Tq, 128) (TPU scratch/IO wants a 128
lane minor); `delta = rowsum(do·o)` is recomputed in-kernel from the o/do
blocks rather than stored.

The backward kernels rebuild a block's probabilities as exp(q·k − lse) and
read `delta` off `out`: their five array residuals — q, k, v as the forward
kernel took them, its output and its logsumexp — have to come from ONE run
of the forward, else a row's probabilities no longer sum to one and `delta`
belongs to another output. So all five carry
`jax.ad_checkpoint.checkpoint_name`s (`RESIDUAL_NAMES`), and a caller that
recomputes its layer in the backward pass (`jax.checkpoint`) passes
`policy=KEEP_RESIDUALS`: the five are then kept from the forward pass, the
recomputation holds no second run of the forward kernel and rebuilds no q, k
or v (on a TPU a recomputed projection is not the forward's to the last bit:
XLA fuses and tiles it differently), and the gradient is that of the
attention the loss was read from. The price is their bytes, from a layer's
forward to its backward: (2·H + 2·Hkv)·Tq·D operand-sized values and H·Tq·128
float32 a batch row. Under a plain `jax.checkpoint`, or none, the names are
the identity.

`q_offset`/`kv_offset` position the local blocks in a GLOBAL sequence for
causal masking, mirroring `full_attention`'s contract. They enter the kernel
as SCALAR-PREFETCH values (SMEM), so they may be TRACED — ring attention
passes a different kv offset each ppermute rotation. `flash_attention_lse`
additionally returns the logsumexp, which is what lets ring attention merge
per-block flash results exactly (see ops.attention._ring_attention_flash).

Two head widths: q and k share one (D), v and the output another (Dv) —
latent attention's keys carry a rotary part their values lack (192 beside 128).
`out`, its cotangent, dv and the forward's accumulator are Dv wide; q, k, dq
and dk D wide; the softmax scale is D^-1/2. Every BlockSpec, scratch shape and
both plans take the two apart, and nothing is padded: a v widened to D would
spend D/Dv of the p·v matmuls, of `out` and of the kept residuals. With
Dv == D the calls, grids, blocks and plans are what they were. A width over
the lane tile that is no multiple of it (192), or under it (64), is a
full-dimension block, which Mosaic lays out in whole tiles: the plans count it
so (`_in_vmem`).

Grouped-query attention: k and v may carry FEWER heads than q (B, T, Hkv, D
with H a multiple of Hkv); query head h reads key-value head h // (H/Hkv)
through the K/V BlockSpec index maps, so no copy of K or V widened to H
heads exists in HBM, forward or backward. dK/dV of a key-value head are
summed over its group's query heads inside the backward kernel (the group's
heads and q blocks share the innermost, revisiting grid axis; the split
route's dkv kernel likewise). With H == Hkv the kernels are what they were.

Blocks wholly above the diagonal are never visited — the resident kernels'
loops end at the diagonal, the streaming ones skip the step (`pl.when`), of
the arithmetic only: their unbanded grid still has a step for every (q block,
kv block) pair, and a skipped step still fetches its K/V block.
Fully-masked ROWS (a q block entirely before every kv position: its loops run
no block) return 0 with lse=NEG_BIG, unlike the XLA path's
finite-NEG_BIG uniform softmax — zero is the defensible answer, the ring
merge relies on the NEG_BIG lse, and no real caller consumes such rows.

Sliding-window attention (`window=W`: key j is visible to query i iff
i − W < j ≤ i, W keys with the query's own position among them) visits a q
block's BAND only: the resident kernels' loops over a q block's kv blocks run
from the band's first block to the diagonal (`_visible_kv`), and where the
head does not fit the grids are banded — the kv axis of the streaming
forward's grid (and of the split route's dq kernel's) has only as many steps
as a q block's band spans kv blocks (`_kv_band`: 2 at bq = bk = W), the K/V
index maps start at the first block the q block can see, and the split
route's dkv kernel's q axis is banded the same way (`_q_band`). So a windowed
layer pays neither the DMA nor the grid steps of the keys it cannot see. A
step of a banded grid past the band's end (the first q blocks' bands are
shorter) is skipped and its index map repeats the previous
block, which fetches nothing. The element mask is applied in the band's edge
blocks only. The windowed kernels carry names of their own
(`flash_attention_swa_fwd`, `_swa_bwd`; split: `_swa_bwd_dq`, `_swa_bwd_dkv`),
so that a trace tells a model's windowed layers from its full ones. Windows
are for UNSHARDED attention: the band's index maps are computed from the block
index alone, so a windowed call takes no offsets (`can_flash` declines one, and
`ops.attention.full_attention` then takes its XLA path). A window of at least
the key length is the causal call, kernel for kernel. Under a window with
zero offsets no row is fully masked (the diagonal is always visible), but a
row can be fully masked WITHIN the first block of its band: the running
maximum then stays NEG_BIG, the block's garbage is multiplied by
exp(NEG_BIG − m) = 0 when the row's first visible key arrives, and nothing of
it is left.

A mask that is DATA (`keep`: int8 or bool, (B, Tq, Tk), one plane a batch row
shared by every head; key j is visible to query i iff j ≤ i AND keep[i, j]) is
a fifth operand of every kernel, read tile by tile through a BlockSpec of
its own — in the resident kernels the q block's whole strip of it, (bq, Tk),
beside the resident k and v. The causal block skip stays and NO block is
skipped for being empty of kept keys: the mask is applied in every block a q
block visits, not in edge blocks only. A row with no kept key WITHIN a block
behaves as the windowed kernels' rows above do, so the contract is that every
row keeps at least one key of its causal prefix (a selection that always keeps
the query's own position does); a row that keeps none returns the mean of the
values it visited, not zero. The kernels carry names of their own
(`flash_attention_sel_fwd`, `_sel_bwd`; split: `_sel_bwd_dq`, `_sel_bwd_dkv`).
The strip costs the resident backward 2·bq·Tk bytes of VMEM, so the backward
of a `keep` call plans q blocks of `SEL_BLOCK_Q` (at 1024 a head of 128 with
16 384 keys no longer fits in two buffers each, only in one); the forward,
with no dk and dv to hold, keeps the default. The resident forward walks the q
blocks in its outer order and the group's heads within (the backward sums dk
and dv over heads first and cannot), so that a strip is fetched once a group
and not once a head: at 16 384 keys and 32 heads on 4, 1.07 GB a call, where
(bq, bk) tiles for the live pairs of every head are 4.4 GB and a strip a head
8.6 GB. `keep` is for UNSHARDED attention
without a window: with a `window` or with offsets `can_flash` declines the
call and `ops.attention.full_attention` takes its XLA path. `keep=None`
traces and compiles exactly what it did before `keep` existed.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_BIG = -1e30  # finite "-inf", matches ops.attention
_LANE = 128      # TPU lane width: minor dims of scratch/residuals
# what the described chip of the rehearsals has (v5e: 128 MiB a core); a
# visible TPU answers for itself
_V5E_VMEM_BYTES = 128 << 20


def _vmem_bytes() -> int:
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except Exception:   # no TPU visible: a rehearsal or interpret mode
        return _V5E_VMEM_BYTES


# Tuned on TPU v5 lite, T=4096 H8 D64 fwd+bwd: (256,256) 14.0ms,
# (512,512) 7.6ms, (512,1024) 5.9ms, (1024,1024) 5.5ms. Large KV blocks
# amortize the per-grid-step overhead; VMEM at (1024,1024) stays ~10 MB
# (the f32 score block dominates: bq*bk*4 = 4 MB).
# Under a window (the banded kernels) the same targets win: T=16384, 32/4
# heads of 128, bfloat16, W=1024 on a v5e, forward / forward + backward ms
# (my chip run, PR 36): (1024,1024) 6.08 / 18.11 — two kv blocks a q block,
# 2048 keys computed for 1024 visible — (512,1024) 6.53 / 18.94, (512,512)
# 8.62 / 18.91, (256,1024) 7.92 / 20.96, (256,512) 9.59 / 22.22, (1024,512)
# 10.28 / 22.91 (FOUR kv blocks of 512 a q block, not three: a q block's
# rows span 2047 keys), (512,256) 14.41 / 28.22, (256,256) 14.33 / 29.75,
# (1024,256) 17.85 / 33.08; (2048,1024) does not fit VMEM. Fewer keys
# computed does not pay for more grid steps. The causal call on the same
# operands: 19.65 / 74.90 at (1024,1024), 4.1 times the banded one for 8.26
# times the visible pairs.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# With a `keep` mask the resident backward also holds the q block's (bq, Tk)
# int8 strip of it, twice: at 16 384 keys, a bfloat16 head of 128 and bq = 1024
# that is 32 MiB more than the 96 MiB a kernel may use leave beside two buffers
# of each whole-head block, at 512 it fits.
SEL_BLOCK_Q = 512

# The names of the custom rule's array residuals, in its order: q, k, v as
# (B, H, T, D), out likewise, logsumexp (B, H, Tq, 128) float32; and the
# `jax.checkpoint` policy that keeps them (the module docstring says why all
# five or none).
RESIDUAL_NAMES = tuple(
    "flash_attention_" + x for x in ("q", "k", "v", "out", "lse"))
KEEP_RESIDUALS = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)


def pick_block(t: int, target: int, min_block: int = 8) -> Optional[int]:
    """Largest power-of-two block <= target that divides t. `min_block` is
    the dtype's sublane tile: 8 for float32, 16 for bfloat16 (Mosaic tiles
    (8,128)/(16,128) respectively — a 16-sublane dtype with an 8-row block
    fails to compile on real TPU, which interpret-mode tests can't catch).
    None when t has no such divisor: caller falls back to the XLA path
    rather than padding."""
    b = 1
    while b * 2 <= min(t, target):
        b *= 2
    while b >= min_block:
        if t % b == 0:
            return b
        b //= 2
    return None


def _min_block(dtype) -> int:
    """Sublane tile floor for the q/k/v dtype (None -> assume float32):
    Mosaic tiles are (8,128) for 4-byte, (16,128) for 2-byte, (32,128) for
    1-byte dtypes."""
    if dtype is None:
        return 8
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize == 1:
        return 32
    if itemsize == 2:
        return 16
    return 8


_INTERPRET_ENV = "EDL_FLASH_INTERPRET"
_warned_probe_broken = False


@contextlib.contextmanager
def interpret_mode():
    """Public entry for interpret-mode testing: wraps
    `pltpu.force_tpu_interpret_mode()` AND marks interpret mode active via
    `EDL_FLASH_INTERPRET` so `_interpret_active` has a signal that does not
    depend on JAX private internals. Tests use THIS, not pltpu directly."""
    prev = os.environ.get(_INTERPRET_ENV)
    os.environ[_INTERPRET_ENV] = "1"
    try:
        with pltpu.force_tpu_interpret_mode():
            yield
    finally:
        if prev is None:
            os.environ.pop(_INTERPRET_ENV, None)
        else:
            os.environ[_INTERPRET_ENV] = prev


def _interpret_active() -> bool:
    """True inside `interpret_mode()` / `pltpu.force_tpu_interpret_mode()`
    (tests run the Mosaic kernel on CPU there).

    Primary signal: the EDL_FLASH_INTERPRET env flag our own
    `interpret_mode()` sets — public, upgrade-proof. Secondary: the JAX
    config state behind pltpu's context manager, probed defensively (it is
    a private module); if that probe breaks after a JAX upgrade we log
    once instead of silently narrowing routing, and interpret_mode() users
    are unaffected."""
    if os.environ.get(_INTERPRET_ENV) == "1":
        return True
    global _warned_probe_broken
    try:
        from jax._src import config as _jax_config

        cm = getattr(
            _jax_config, "pallas_tpu_interpret_mode_context_manager", None
        )
        if cm is None:
            raise AttributeError(
                "pallas_tpu_interpret_mode_context_manager missing"
            )
        return cm.value is not None
    except Exception as e:
        if not _warned_probe_broken:
            _warned_probe_broken = True
            from elasticdl_tpu.common.log_utils import default_logger

            default_logger(__name__).warning(
                "interpret-mode probe of jax._src.config failed (%s); "
                "bare force_tpu_interpret_mode() is now invisible — use "
                "elasticdl_tpu.ops.pallas_attention.interpret_mode()", e,
            )
        return False


def kernel_interpret(requested: Optional[bool] = None) -> bool:
    """The `interpret=` value for a pallas_call traced now: `requested`,
    or the ambient `_interpret_active()` signal when None. Interpret mode
    is what the CPU tests run the kernels under; on a TPU backend it would
    execute the kernel as plain XLA ops under the chip's name, so there it
    is an error, not a route."""
    interpret = _interpret_active() if requested is None else bool(requested)
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode is active on a TPU backend "
            f"({_INTERPRET_ENV}=1 or force_tpu_interpret_mode): the kernel "
            "would run interpreted instead of compiled by Mosaic. Interpret "
            "mode is for CPU tests only; unset it on the chip."
        )
    return interpret


def _causal_p_mask(p, q_start, kv_start, block_q, block_k, window=None):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    visible = kv_pos <= q_pos
    if window is not None:
        visible &= kv_pos > q_pos - window
    return jnp.where(visible, p, 0.0) if p is not None else visible


def _visible(masked, keep, q_start, kv_start, block_q, block_k, window=None):
    """The element mask of a block, or None where every pair is visible: the
    positions' (`masked`: an edge block) and the data's (`keep`: the block of
    the int8 plane, applied in EVERY block)."""
    visible = (_causal_p_mask(None, q_start, kv_start, block_q, block_k, window)
               if masked else None)
    if keep is not None:
        kept = keep.astype(jnp.int32) != 0
        visible = kept if visible is None else visible & kept
    return visible


# ------------------------------------------------------------------ the band
# Under a window W (zero offsets) q block i sees the kv blocks
# first_kv(i) .. last_kv(i), and kv block x is seen by the q blocks
# first_q(x) .. last_q(x). The same expressions serve the index maps and the
# kernels (traced block indices) and, with Python's own `max` and `min`, the
# static step counts.


def _first_kv(i, bq, bk, window, maximum=jnp.maximum):
    return maximum(i * bq - window + 1, 0) // bk


def _last_kv(i, bq, bk, num_kv, minimum=jnp.minimum):
    return minimum(((i + 1) * bq - 1) // bk, num_kv - 1)


def _first_q(x, bq, bk):
    return (x * bk) // bq


def _last_q(x, bq, bk, window, num_q, minimum=jnp.minimum):
    return minimum(((x + 1) * bk + window - 2) // bq, num_q - 1)


def _kv_band(num_q, num_kv, bq, bk, window):
    """(steps of the banded kv axis, blocks that compute a head): the most kv
    blocks any q block's band spans, and their sum over the q blocks."""
    spans = [_last_kv(i, bq, bk, num_kv, min) - _first_kv(i, bq, bk, window, max) + 1
             for i in range(num_q)]
    return max(spans), sum(spans)


def _q_band(num_q, num_kv, bq, bk, window):
    """The most q blocks that see any one kv block."""
    return max(_last_q(x, bq, bk, window, num_q, min) - _first_q(x, bq, bk) + 1
               for x in range(num_kv))


def _block_kind(q_start, kv_start, block_q, block_k, window, in_range):
    """(live, inner) of a windowed block: `live` if any of its (query, key)
    pairs is visible and the block exists (`in_range`: a step past the band's
    end), `inner` if every pair is, so that it needs no element mask."""
    live = (in_range & (kv_start <= q_start + block_q - 1)
            & (kv_start + block_k - 1 > q_start - window))
    inner = ((kv_start + block_k - 1 <= q_start)
             & (kv_start >= q_start + block_q - window))
    return live, inner


def _when_banded(live, inner, body):
    """Run `body(masked)` for a live block: with the element mask in an edge
    block, without it inside the band."""
    pl.when(live & inner)(lambda: body(False))
    pl.when(live & jnp.logical_not(inner))(lambda: body(True))


def _visible_kv(q_start, kv_off, *, causal, block_q, block_k, num_kv, window):
    """(lo, lo_in, hi_in, hi) of the kv blocks a q block whose first row is
    `q_start` sees among `num_kv` that start at `kv_off`: lo..hi-1 hold a
    visible pair, lo_in..hi_in-1 of them only visible pairs (no element mask),
    so that lo..lo_in-1 (the band's lower edge, under a window) and
    hi_in..hi-1 (the diagonal's blocks) are the masked ones. Traced: the
    offsets come from SMEM."""
    if not causal:
        return 0, 0, num_kv, num_kv
    rel = q_start - kv_off                      # the q block's first row, in keys
    # kv block j is live iff j·bk <= rel + bq − 1, whole iff j·bk + bk − 1 <= rel
    hi = jnp.minimum(jnp.maximum(rel + block_q - 1 + block_k, 0) // block_k, num_kv)
    hi_in = jnp.minimum(jnp.maximum(rel + 1, 0) // block_k, hi)
    if window is None:
        return 0, 0, hi_in, hi
    # ... and j·bk + bk − 1 > rel − W, whole iff j·bk >= rel + bq − W
    lo = jnp.minimum(jnp.maximum(rel - window + 1, 0) // block_k, hi)
    lo_in = jnp.clip((jnp.maximum(rel + block_q - window, 0) + block_k - 1) // block_k,
                     lo, hi)
    return lo, lo_in, jnp.maximum(hi_in, lo_in), hi


def _for_visible_kv(visit, q_start, kv_off, *, causal, block_q, block_k, num_kv,
                    window):
    """Run `visit(masked)`'s loop body over the kv blocks a q block sees, kv
    blocks ascending, each loop's body straight-line: the band's masked lower
    edge (a window's), the whole blocks, the diagonal's."""
    lo, lo_in, hi_in, hi = _visible_kv(
        q_start, kv_off, causal=causal, block_q=block_q, block_k=block_k,
        num_kv=num_kv, window=window)
    if window is not None:
        jax.lax.fori_loop(lo, lo_in, visit(True), 0)
    jax.lax.fori_loop(lo_in, hi_in, visit(False), 0)
    if causal:
        jax.lax.fori_loop(hi_in, hi, visit(True), 0)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct that propagates `like`'s varying-mesh-axes set —
    required for pallas_call outputs inside a shard_map manual region
    (check_vma insists outputs declare their variance)."""
    vma = getattr(getattr(like, "aval", None), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _kv_head_of(heads: int, kv_heads: int):
    """query head -> the key-value head it reads (grouped-query attention:
    `heads // kv_heads` query heads share one). The identity, with no
    arithmetic in the index map, when every query head has its own."""
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads do not divide over "
                         f"{kv_heads} key-value heads")
    group = heads // kv_heads
    return (lambda h: h) if group == 1 else (lambda h: h // group)


# ---------------------------------------------------------------- forward


def _kernel_name(part: str, window, keep: bool = False) -> str:
    kind = "swa_" if window is not None else "sel_" if keep else ""
    return f"flash_attention_{kind}{part}"


def _softmax_init(acc, m_scr, l_scr):
    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[:] = jnp.zeros_like(l_scr)


def _softmax_step(q, k, v, mask, acc, m_scr, l_scr, scale):
    """One (q block, kv block) pair of the online-softmax recurrence, the
    same values on both forward routes. q (bq, D), k (bk, D), v (bk, Dv), the
    accumulator (bq, Dv); `mask`: the block's element mask or None."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                   # (bq, bk)
    if mask is not None:
        s = jnp.where(mask, s, NEG_BIG)

    m_prev = m_scr[:, :1]                       # (bq, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                      # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)             # (bq, 1)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc[:] = acc[:] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _softmax_finalize(o_ref, lse_ref, acc, m_scr, l_scr):
    l = l_scr[:, :1]
    o_ref[0, 0] = (acc[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # lse of a fully-masked row: m stays NEG_BIG and l stays 0 -> the
    # log floor keeps it at ~NEG_BIG, which the ring merge treats as
    # "no contribution"
    lse_ref[0, 0] = jnp.broadcast_to(
        m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30)), lse_ref.shape[2:]
    )


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref,
                acc, m_scr, l_scr, *, scale, causal, block_q, block_k,
                num_kv, window=None, kv_blocks=None):
    """The STREAMING forward: a grid step a (q block, kv block) pair.
    `num_kv`: the steps of the grid's kv axis — every kv block, or under a
    `window` the band's steps, of `kv_blocks` kv blocks in all. `keep_ref`:
    the (1, bq, bk) block of the data mask, or None."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off = offs_ref[0]
    kv_off = offs_ref[1]

    pl.when(j == 0)(lambda: _softmax_init(acc, m_scr, l_scr))

    # the kv block of this step: under a window the band starts at the first
    # block the q block can see
    jb = j if window is None else _first_kv(i, block_q, block_k, window) + j
    q_start = q_off + i * block_q
    kv_start = kv_off + jb * block_k

    def _accumulate(masked):
        mask = _visible(masked, None if keep_ref is None else keep_ref[0],
                        q_start, kv_start, block_q, block_k, window)
        _softmax_step(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], mask,
                      acc, m_scr, l_scr, scale)

    if window is None:
        # causal: skip KV blocks entirely above the diagonal (traced predicate
        # — offsets come from SMEM, so this is runtime block skipping)
        live = True if not causal else kv_start <= q_start + block_q - 1
        pl.when(live)(lambda: _accumulate(causal))
    else:
        _when_banded(*_block_kind(q_start, kv_start, block_q, block_k, window,
                                  jb <= kv_blocks - 1), _accumulate)

    pl.when(j == num_kv - 1)(
        lambda: _softmax_finalize(o_ref, lse_ref, acc, m_scr, l_scr))


def _fwd_resident_kernel(offs_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref,
                         acc, m_scr, l_scr, *, scale, causal, block_q, block_k,
                         num_kv, group=1, window=None):
    """The RESIDENT forward. One step: a q block of one query head against
    every kv block it sees of its key-value head's WHOLE k and v, which stay
    in VMEM while the innermost axis walks the q blocks and, for each, the
    `group` query heads that read them. `keep_ref`: the q block's (1, bq, Tk)
    strip of the data mask, or None."""
    y = pl.program_id(2)
    i = y if group == 1 else y // group
    q_start = offs_ref[0] + i * block_q
    kv_off = offs_ref[1]
    _softmax_init(acc, m_scr, l_scr)
    q = q_ref[0, 0]

    def visit(masked):
        def body(j, carry):
            rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            mask = _visible(masked, None if keep_ref is None else keep_ref[0, :, rows],
                            q_start, kv_off + j * block_k, block_q, block_k, window)
            _softmax_step(q, k_ref[0, 0, rows, :], v_ref[0, 0, rows, :], mask,
                          acc, m_scr, l_scr, scale)
            return carry
        return body

    _for_visible_kv(visit, q_start, kv_off, causal=causal, block_q=block_q,
                    block_k=block_k, num_kv=num_kv, window=window)
    _softmax_finalize(o_ref, lse_ref, acc, m_scr, l_scr)


def _banded_kv_at(bq, bk, window, num_kv):
    """(q block, step) -> the kv block a banded grid's step reads: the band's
    own, or past its end the band's last again (no new fetch)."""
    return lambda i, j: jnp.minimum(_first_kv(i, bq, bk, window) + j,
                                    _last_kv(i, bq, bk, num_kv))


def _with_optional(kernel, first, present, **static):
    """`kernel` taking its optional references — one for each entry of
    `present`, from position `first` on — or None where the call has none."""
    def call(*refs):
        refs = list(refs)
        for at, there in enumerate(present, first):
            if not there:
                refs.insert(at, None)
        kernel(*refs, **static)
    return call


def _keep_kv_at(causal, bq, bk, num_kv):
    """(q block, step) -> the kv block of the data mask a step of an unbanded
    grid reads: a step above the diagonal is skipped, and its index repeats the
    diagonal's block, which fetches nothing (a `keep` call has no offsets)."""
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, _last_kv(i, bq, bk, num_kv))


class Plan(NamedTuple):
    route: str       # a head's keys "resident" in VMEM, or the other route
    vmem_bytes: int  # what the resident kernel's blocks, scratch and values take
    vmem_limit: int  # what Mosaic may use
    buffers: int = 2  # of each block the resident kernel fetches once a head


def _plan(part, other, what, needs, t_k, head_dim, v_dim, dtype_name, bq, bk, vmem,
          keep):
    """`needs`: the resident kernel's bytes by the buffers it gives a whole-head
    block, most first; the plan is the first that fits, else `other`'s."""
    limit = vmem * 3 // 4
    for buffers, need in needs.items():
        if need <= limit:
            break
    plan = Plan("resident" if need <= limit else other, need, limit, buffers)
    # once a shape and process: which kernel this shape takes
    head = f"head {head_dim}" if v_dim == head_dim else f"head {head_dim} | v {v_dim}"
    logger.info(
        "flash attention's %s (%d keys, %s, %s, blocks %d x %d%s) "
        "takes the %s route: a head's %s resident in VMEM, %s each, need "
        "%d bytes of the %d a kernel may use here", part, t_k, head, dtype_name,
        bq, bk, ", a data mask" if keep else "", plan.route, what,
        {1: "one buffer", 2: "two buffers"}[buffers], need, limit)
    return plan


def _in_vmem(dim: int) -> int:
    """The lanes a minor dimension of `dim` takes in VMEM: whole lane tiles,
    whether it is wider than one and no multiple of it (q and k heads of 192:
    two) or narrower (a head of 64: one, half of it padding — counted "as it
    is" until PR 62, when the compiler refused a resident backward at 32 768
    keys of 64 that the plan had put at 76.0 MB: with two buffers for each
    whole-head block it takes 99.5 of the 96 a kernel may use; with one, which
    the plan gives that shape since PR 63, it compiles and runs)."""
    return -(-dim // _LANE) * _LANE


@functools.lru_cache(maxsize=None)
def _fwd_plan(t_k: int, head_dim: int, dtype_name: str, bq: int, bk: int,
              vmem: int, keep: bool = False, v_dim: Optional[int] = None) -> Plan:
    """`head_dim`: q's and k's; `v_dim`: v's and the output's (None: the same)."""
    size = jnp.dtype(dtype_name).itemsize
    v_dim = v_dim or head_dim
    qk, vo = _in_vmem(head_dim), _in_vmem(v_dim)
    # two buffers each of k and v; a step: two buffers each of q and out, of
    # the logsumexp; the float32 accumulator, maximum and sum; the float32
    # (bq, bk) values (s, p and two more) and v's float32 form; with a data
    # mask two buffers of the q block's int8 strip and a block of it widened
    need = (2 * t_k * (qk + vo) * size
            + 2 * bq * (qk + vo) * size + 16 * bq * _LANE + 4 * bq * vo
            + 16 * bq * bk + 4 * bk * vo
            + (2 * bq * t_k + 4 * bq * bk if keep else 0))
    return _plan("forward", "streaming", "k and v", {2: need}, t_k, head_dim, v_dim,
                 dtype_name, bq, bk, vmem, keep)


def fwd_route(t_k: int, head_dim: int, dtype, bq: int, bk: int,
              keep: bool = False, v_dim: Optional[int] = None) -> Plan:
    """Which forward a call of `t_k` keys a head takes — `resident`: the
    key-value head's whole k and v in VMEM, fetched once a head, and a grid
    step a q block, which loops over the kv blocks it sees; or `streaming`: a
    grid step and a K/V fetch a (q block, kv block) pair, where the head does
    not fit. A pure function of the shapes, the dtype, whether the call has a
    data mask (`keep`: the q block's strip of it sits beside k and v) and the
    chip's VMEM; nothing a caller sets. `head_dim` is q's and k's, `v_dim` v's
    and the output's where it is another (latent attention's 192 and 128)."""
    return _fwd_plan(t_k, head_dim, jnp.dtype(dtype).name, bq, bk, _vmem_bytes(),
                     bool(keep), v_dim or head_dim)


def _flash_fwd(offs, qt, kt, vt, keep=None, *, causal, bq, bk, interpret,
               window=None):
    """offs: (2,) int32 [q_off, kv_off]; qt/kt: (B, H, T, D), vt: (B, H, T,
    Dv); keep: None or (B, Tq, Tk) int8. (out (B, H, Tq, Dv), logsumexp (B, H,
    Tq, 128) float32), by the route `fwd_route` gives: the same values to the
    bit by either."""
    B, H, Tq, D = qt.shape
    Hkv, Tk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    num_q, num_kv = Tq // bq, Tk // bk
    kv_head = _kv_head_of(H, Hkv)
    plan = fwd_route(Tk, D, qt.dtype, bq, bk, keep is not None, Dv)
    static = dict(scale=D ** -0.5, causal=causal, block_q=bq, block_k=bk,
                  window=window)
    if plan.route == "resident":
        # grid axis 1 counts KEY-VALUE heads; the sequential axis 2 walks the
        # q blocks and, for each, the group's query heads (y = q block · group
        # + head in group): the q block's strip of a data mask is then
        # fetched once a group and not once a head
        group = H // Hkv
        kernel = _with_optional(_fwd_resident_kernel, 4, (keep is not None,),
                                num_kv=num_kv, group=group, **static)
        grid = (B, Hkv, num_q * group)
        if group == 1:
            q_at = lambda b, h, y, offs: (b, h, y, 0)
        else:
            q_at = lambda b, h, y, offs: (b, h * group + y % group, y // group, 0)
        kv_spec = lambda d: pl.BlockSpec((1, 1, Tk, d), lambda b, h, y, offs: (b, h, 0, 0))
        keep_spec = pl.BlockSpec((1, bq, Tk), lambda b, h, y, offs: (b, y // group, 0))
    else:
        if window is None:
            steps, kv_at = num_kv, lambda i, j: j
        else:
            steps = _kv_band(num_q, num_kv, bq, bk, window)[0]
            kv_at = _banded_kv_at(bq, bk, window, num_kv)
        kernel = _with_optional(_fwd_kernel, 4, (keep is not None,), num_kv=steps,
                                kv_blocks=num_kv, **static)
        grid = (B, H, num_q, steps)
        q_at = lambda b, h, i, j, offs: (b, h, i, 0)
        kv_spec = lambda d: pl.BlockSpec(
            (1, 1, bk, d), lambda b, h, i, j, offs: (b, kv_head(h), kv_at(i, j), 0))
        keep_at = _keep_kv_at(causal, bq, bk, num_kv)
        keep_spec = pl.BlockSpec((1, bq, bk),
                                 lambda b, h, i, j, offs: (b, i, keep_at(i, j)))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((1, 1, bq, D), q_at), kv_spec(D), kv_spec(Dv)]
            + ([keep_spec] if keep is not None else []),
            out_specs=[pl.BlockSpec((1, 1, bq, Dv), q_at),
                       pl.BlockSpec((1, 1, bq, _LANE), q_at)],
            scratch_shapes=[
                pltpu.VMEM((bq, Dv), jnp.float32),
                pltpu.VMEM((bq, _LANE), jnp.float32),
                pltpu.VMEM((bq, _LANE), jnp.float32),
            ],
        ),
        out_shape=[
            _sds((B, H, Tq, Dv), qt.dtype, qt),
            _sds((B, H, Tq, _LANE), jnp.float32, qt),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=plan.vmem_limit),
        interpret=interpret,
        name=_kernel_name("fwd", window, keep is not None),
    )(offs, qt, kt, vt, *(() if keep is None else (keep,)))
    return out, lse


# ---------------------------------------------------------------- backward


def _p_and_ds(q, k, v, do, lse, delta, *, scale, masked, q_start, kv_start,
              block_q, block_k, window=None, keep=None):
    """Recompute the (bq, bk) p block from saved lse, and ds = p*(dp-delta).
    lse/delta: (bq, 1) float32. `masked`: apply the element mask (causal, and
    the window's lower bound if there is one); `keep`: the (bq, bk) block of
    the data mask, applied whether `masked` or not."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.exp(s - lse)
    visible = _visible(masked, keep, q_start, kv_start, block_q, block_k, window)
    if visible is not None:
        p = jnp.where(visible, p, 0.0)
    dp = jax.lax.dot_general(
        do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (bq, bk)
    ds = p * (dp - delta)
    return p, ds


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   glse_ref, keep_ref, dq_ref, dq_acc, delta_scr, *, scale, causal,
                   block_q, block_k, num_kv, window=None, kv_blocks=None):
    """`num_kv`, `window`, `kv_blocks`: as `_fwd_kernel`'s."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off = offs_ref[0]
    kv_off = offs_ref[1]

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        # dL/ds = p*(dp - delta) + g_lse*p = p*(dp - (delta - g_lse)):
        # the lse cotangent folds into delta (dlse/ds_k = p_k)
        delta_scr[:] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True)
            - (glse_ref[0, 0, :, :1] if glse_ref is not None else 0.0),
            delta_scr.shape)

    jb = j if window is None else _first_kv(i, block_q, block_k, window) + j
    q_start = q_off + i * block_q
    kv_start = kv_off + jb * block_k

    def _accumulate(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        _, ds = _p_and_ds(
            q, k, v_ref[0, 0], do, lse_ref[0, 0, :, :1], delta_scr[:, :1],
            scale=scale, masked=masked, q_start=q_start, kv_start=kv_start,
            block_q=block_q, block_k=block_k, window=window,
            keep=None if keep_ref is None else keep_ref[0])
        dq_acc[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    if window is None:
        live = True if not causal else kv_start <= q_start + block_q - 1
        pl.when(live)(lambda: _accumulate(causal))
    else:
        _when_banded(*_block_kind(q_start, kv_start, block_q, block_k, window,
                                  jb <= kv_blocks - 1), _accumulate)

    @pl.when(j == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    glse_ref, keep_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    causal, block_q, block_k, num_q, group=1, window=None,
                    q_blocks=None):
    """`num_q`: the steps a query head takes of the innermost axis — every q
    block, or under a `window` the steps of the band of q blocks that see
    this kv block, of `q_blocks` q blocks in all."""
    kv = pl.program_id(2)
    # the innermost axis walks the q blocks of every query head that reads
    # this key-value head: `group` heads of num_q blocks each
    inner = pl.program_id(3)
    qi = inner if group == 1 else inner % num_q
    if window is not None:
        qi = _first_q(kv, block_q, block_k) + qi
    q_off = offs_ref[0]
    kv_off = offs_ref[1]

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = q_off + qi * block_q
    kv_start = kv_off + kv * block_k

    def _accumulate(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)   # (bq, 1)
        if glse_ref is not None:
            delta = delta - glse_ref[0, 0, :, :1]
        p, ds = _p_and_ds(
            q, k, v_ref[0, 0], do, lse_ref[0, 0, :, :1], delta,
            scale=scale, masked=masked, q_start=q_start, kv_start=kv_start,
            block_q=block_q, block_k=block_k, window=window,
            keep=None if keep_ref is None else keep_ref[0])
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (bk, D)
        dk_acc[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (bk, D)

    if window is None:
        live = True if not causal else kv_start <= q_start + block_q - 1
        pl.when(live)(lambda: _accumulate(causal))
    else:
        _when_banded(*_block_kind(q_start, kv_start, block_q, block_k, window,
                                  qi <= q_blocks - 1), _accumulate)

    @pl.when(inner == group * num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                glse_ref, keep_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                scale, causal, block_q, block_k, num_q, num_kv, group=1,
                window=None):
    """One step: a q block of one query head against every kv block it sees
    of its key-value head's WHOLE k and v, which stay in VMEM — as the float32
    dk and dv of the head do — while the innermost axis walks the `group`
    query heads that read them, `num_q` q blocks each. The score block of a
    (q block, kv block) pair is computed once and feeds dq, dk and dv.
    `keep_ref`: the q block's (1, bq, Tk) strip of the data mask, or None."""
    y = pl.program_id(2)
    i = y if group == 1 else y % num_q
    q_start = offs_ref[0] + i * block_q
    kv_off = offs_ref[1]

    @pl.when(y == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0]
    q32 = q.astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    o = o_ref[0, 0].astype(jnp.float32)
    # dL/ds = p*(dp - delta) + g_lse*p = p*(dp - (delta - g_lse)):
    # the lse cotangent folds into delta (dlse/ds_k = p_k)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)         # (bq, 1)
    if glse_ref is not None:
        delta = delta - glse_ref[0, 0, :, :1]
    lse = lse_ref[0, 0, :, :1]
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def visit(masked):
        def body(j, carry):
            rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            k = k_ref[0, 0, rows, :]
            p, ds = _p_and_ds(
                q, k, v_ref[0, 0, rows, :], do, lse, delta, scale=scale,
                masked=masked, q_start=q_start, kv_start=kv_off + j * block_k,
                block_q=block_q, block_k=block_k, window=window,
                keep=None if keep_ref is None else keep_ref[0, :, rows])
            dv_acc[rows, :] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                # (bk, D)
            dk_acc[rows, :] += jax.lax.dot_general(
                ds, q32, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                        # (bk, D)
            dq_acc[:] += jax.lax.dot_general(
                ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                        # (bq, D)
            return carry
        return body

    _for_visible_kv(visit, q_start, kv_off, causal=causal, block_q=block_q,
                    block_k=block_k, num_kv=num_kv, window=window)
    dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(y == group * num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _bwd_plan(t_k: int, head_dim: int, dtype_name: str, bq: int, bk: int,
              vmem: int, keep: bool = False, v_dim: Optional[int] = None) -> Plan:
    """`head_dim`: q's, k's, dq's and dk's; `v_dim`: v's, the output's, its
    cotangent's and dv's (None: the same)."""
    size = jnp.dtype(dtype_name).itemsize
    v_dim = v_dim or head_dim
    qk, vo = _in_vmem(head_dim), _in_vmem(v_dim)
    # `buffers` each of k, v in and dk, dv out — the pipeline's two, or where
    # those do not fit ONE (their block index changes once a head: the second
    # buffer buys the overlap of one fetch and one write-back a head); dk, dv
    # in float32
    resident = lambda buffers: t_k * (qk + vo) * (2 * buffers * size + 4)
    # a step: two buffers each of q, o, do in and dq out, of lse and its
    # cotangent; dq in float32; the float32 (bq, bk) values (s, p, dp, ds and
    # a transposed operand) and float32 forms of q, do, o, k, v; with a data
    # mask two buffers of the q block's int8 strip and a block of it widened
    step = (4 * bq * (qk + vo) * size + 16 * bq * _LANE + 4 * bq * qk
            + 20 * bq * bk + 4 * (bq * (qk + 2 * vo) + bk * (qk + vo))
            + (2 * bq * t_k + 4 * bq * bk if keep else 0))
    return _plan("backward", "split", "k, v, dk and dv",
                 {buffers: resident(buffers) + step for buffers in (2, 1)}, t_k,
                 head_dim, v_dim, dtype_name, bq, bk, vmem, keep)


def bwd_route(t_k: int, head_dim: int, dtype, bq: int, bk: int,
              keep: bool = False, v_dim: Optional[int] = None) -> Plan:
    """Which backward a call of `t_k` keys a head takes — `resident`: ONE
    kernel, the key-value head's whole k and v and its float32 dk and dv in
    VMEM, a pair's score block computed once for dq, dk and dv, the head's k,
    v, dk and dv blocks in the pipeline's two buffers each or, where only that
    fits, in one (`Plan.buffers`); or `split`: a dq kernel and a dkv kernel
    that each stream kv blocks and each recompute the score block, where the
    head fits neither way. A pure function of the
    shapes, the dtype, whether the call has a data mask (`keep`: its strip
    sits beside k and v) and the chip's VMEM; nothing a caller sets. `head_dim`
    and `v_dim` as `fwd_route`'s."""
    return _bwd_plan(t_k, head_dim, jnp.dtype(dtype).name, bq, bk, _vmem_bytes(),
                     bool(keep), v_dim or head_dim)


def _flash_bwd(res, g, g_lse, *, causal, bq, bk, interpret, window=None):
    """g: cotangent of out (B, T, H, Dv); g_lse: cotangent of lse (B, H, Tq)
    or None (out-only variant). `res` ends with the data mask where the call
    had one."""
    offs, qt, kt, vt, ot, lse, *keep = res       # (B, H, T, D or Dv) / lse 4D
    B, H, Tq, D = qt.shape
    gt = g.transpose(0, 2, 1, 3)                 # (B, H, Tq, Dv)
    operands = (offs, qt, kt, vt, ot, gt, lse)
    if g_lse is not None:
        operands += (jnp.broadcast_to(
            g_lse.astype(jnp.float32)[..., None], (B, H, Tq, _LANE)),)
    operands += tuple(keep)
    plan = bwd_route(kt.shape[2], D, qt.dtype, bq, bk, bool(keep), vt.shape[3])
    static = dict(causal=causal, bq=bq, bk=bk, interpret=interpret, window=window,
                  optional=(g_lse is not None, bool(keep)))
    if plan.route == "resident":
        dq, dk, dv = _bwd_resident(operands, plan.vmem_limit, plan.buffers, **static)
    else:
        dq, dk, dv = _bwd_split(operands, **static)
    back = lambda x: x.transpose(0, 2, 1, 3)
    return (None, back(dq), back(dk), back(dv)) + (None,) * len(keep)


def _bwd_resident(operands, vmem_limit, buffers, *, causal, bq, bk, interpret,
                  window, optional):
    """(dq, dk, dv) as (B, H, T, D) by `_bwd_kernel`: grid axis 1 counts
    KEY-VALUE heads, the sequential axis 2 walks the group's query heads and
    their q blocks (y = head in group · num_q + q block). `buffers`: the
    plan's, of the whole-head blocks (k, v in; dk, dv out) — the pipeline's two
    (nothing is said, and the call is what it was), or one. `optional`: whether
    the operands end with (the logsumexp's cotangent, the data mask)."""
    _, qt, kt, vt = operands[:4]
    B, H, Tq, D = qt.shape
    Hkv, Tk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    num_q, group = Tq // bq, H // Hkv
    with_glse, with_keep = optional
    if group == 1:
        q_at = lambda b, h, y, offs: (b, h, y, 0)
    else:
        q_at = lambda b, h, y, offs: (b, h * group + y // num_q, y % num_q, 0)
    q_spec, o_spec = (pl.BlockSpec((1, 1, bq, d), q_at) for d in (D, Dv))
    lse_spec = pl.BlockSpec((1, 1, bq, _LANE), q_at)
    once_a_head = None if buffers == 2 else pl.Buffered(buffers)
    k_spec, v_spec = (pl.BlockSpec((1, 1, Tk, d), lambda b, h, y, offs: (b, h, 0, 0),
                                   pipeline_mode=once_a_head)
                      for d in (D, Dv))
    # the q block's strip of the mask: every key, as k and v are whole
    keep_spec = pl.BlockSpec((1, bq, Tk), lambda b, h, y, offs: (b, y % num_q, 0))
    return pl.pallas_call(
        _with_optional(_bwd_kernel, 7, optional, scale=D ** -0.5, causal=causal,
                       block_q=bq, block_k=bk, num_q=num_q, num_kv=Tk // bk,
                       group=group, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, group * num_q),
            in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, lse_spec]
            + ([lse_spec] if with_glse else [])
            + ([keep_spec] if with_keep else []),
            out_specs=[q_spec, k_spec, v_spec],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((Tk, D), jnp.float32),
                pltpu.VMEM((Tk, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            _sds(qt.shape, qt.dtype, qt),
            _sds(kt.shape, kt.dtype, kt),
            _sds(vt.shape, vt.dtype, vt),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=_kernel_name("bwd", window, with_keep),
    )(*operands)


def _bwd_split(operands, *, causal, bq, bk, interpret, window, optional):
    """(dq, dk, dv) as (B, H, T, D) by the dq kernel and the dkv kernel, each
    streaming kv (q) blocks through Mosaic's default VMEM. `optional`: as
    `_bwd_resident`'s."""
    _, qt, kt, vt = operands[:4]
    B, H, Tq, D = qt.shape
    Hkv, Tk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    num_q, num_kv = Tq // bq, Tk // bk
    scale = D ** -0.5
    kv_head, group = _kv_head_of(H, Hkv), H // Hkv
    with_glse, with_keep = optional
    if window is None:
        kv_steps, kv_at = num_kv, lambda i, j: j
        q_steps, q_block_at = num_q, lambda x, y: y
    else:
        kv_steps = _kv_band(num_q, num_kv, bq, bk, window)[0]
        kv_at = _banded_kv_at(bq, bk, window, num_kv)
        q_steps = _q_band(num_q, num_kv, bq, bk, window)
        # past the band's end its last q block again: no new fetch
        q_block_at = lambda x, y: jnp.minimum(
            _first_q(x, bq, bk) + y, _last_q(x, bq, bk, window, num_q))

    q_spec, o_spec = (pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j, offs: (b, h, i, 0))
                      for d in (D, Dv))
    k_spec, v_spec = (
        pl.BlockSpec((1, 1, bk, d),
                     lambda b, h, i, j, offs: (b, kv_head(h), kv_at(i, j), 0))
        for d in (D, Dv))
    lse_spec = pl.BlockSpec((1, 1, bq, _LANE),
                            lambda b, h, i, j, offs: (b, h, i, 0))
    keep_kv = _keep_kv_at(causal, bq, bk, num_kv)
    keep_spec = pl.BlockSpec((1, bq, bk),
                             lambda b, h, i, j, offs: (b, i, keep_kv(i, j)))

    dq = pl.pallas_call(
        _with_optional(_bwd_dq_kernel, 7, optional, scale=scale, causal=causal,
                       block_q=bq, block_k=bk, num_kv=kv_steps, window=window,
                       kv_blocks=num_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, num_q, kv_steps),
            in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, lse_spec]
            + ([lse_spec] if with_glse else [])
            + ([keep_spec] if with_keep else []),
            out_specs=[q_spec],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, _LANE), jnp.float32),
            ],
        ),
        out_shape=[_sds(qt.shape, qt.dtype, qt)],
        interpret=interpret,
        name=_kernel_name("bwd_dq", window, with_keep),
    )(*operands)[0]

    # dk/dv sweep: kv block outer (revisited output), q block inner.
    # grid axis 1 counts KEY-VALUE heads; y walks the group's query heads and
    # their q blocks (y = head_in_group * q_steps + step: the q block itself,
    # or under a window the step of the band that sees kv block x)
    if group == 1:
        q_at = lambda b, h, x, y, offs: (b, h, q_block_at(x, y), 0)
    else:
        q_at = lambda b, h, x, y, offs: (
            b, h * group + y // q_steps, q_block_at(x, y % q_steps), 0)
    q_spec2, o_spec2 = (pl.BlockSpec((1, 1, bq, d), q_at) for d in (D, Dv))
    k_spec2, v_spec2 = (pl.BlockSpec((1, 1, bk, d), lambda b, h, x, y, offs: (b, h, x, 0))
                        for d in (D, Dv))
    lse_spec2 = pl.BlockSpec((1, 1, bq, _LANE), q_at)
    keep_q = (lambda x, y: jnp.maximum(y, _first_q(x, bq, bk))) if causal \
        else (lambda x, y: y)
    keep_spec2 = pl.BlockSpec(
        (1, bq, bk), lambda b, h, x, y, offs: (b, keep_q(x, y % q_steps), x))
    dk, dv = pl.pallas_call(
        _with_optional(_bwd_dkv_kernel, 7, optional, scale=scale, causal=causal,
                       block_q=bq, block_k=bk, num_q=q_steps, group=group,
                       window=window, q_blocks=num_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, num_kv, group * q_steps),
            in_specs=[q_spec2, k_spec2, v_spec2, o_spec2, o_spec2,
                      lse_spec2] + ([lse_spec2] if with_glse else [])
            + ([keep_spec2] if with_keep else []),
            out_specs=[k_spec2, v_spec2],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            _sds(kt.shape, kt.dtype, kt),
            _sds(vt.shape, vt.dtype, vt),
        ],
        interpret=interpret,
        name=_kernel_name("bwd_dkv", window, with_keep),
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------- public


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, fwd_blocks: Tuple[int, int], bq: int, bk: int,
                interpret: bool, with_lse: bool, window: Optional[int] = None):
    """Returns flash(offs, q, k, v) -> out, or (out, lse(B, H, Tq)) when
    `with_lse` — the lse variant also backpropagates lse's cotangent (the
    ring merge differentiates through it). `fwd_blocks` are the forward
    kernel's (block_q, block_k), `bq` and `bk` the backward's: the residuals
    are what they are whatever blocks made them. With a `window` the kernels
    run their bands. A fifth argument, where a call gives one, is the
    data mask `keep` (B, Tq, Tk) int8: it rides to the kernels and into the
    residuals, and has no cotangent."""

    def _fwd_transposed(offs, q, k, v, *keep):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out, lse = _flash_fwd(offs, qt, kt, vt, *keep, causal=causal,
                              bq=fwd_blocks[0], bk=fwd_blocks[1],
                              interpret=interpret, window=window)
        # named here, where the residuals are made, so that nothing reads an
        # un-named one
        return (offs, *map(checkpoint_name, (qt, kt, vt, out, lse),
                           RESIDUAL_NAMES), *keep)

    @jax.custom_vjp
    def flash(offs, q, k, v, *keep):
        res = _fwd_transposed(offs, q, k, v, *keep)
        out = res[4].transpose(0, 2, 1, 3)
        return (out, res[5][..., 0]) if with_lse else out

    def fwd(offs, q, k, v, *keep):
        res = _fwd_transposed(offs, q, k, v, *keep)
        out = res[4].transpose(0, 2, 1, 3)
        return ((out, res[5][..., 0]) if with_lse else out), res

    def bwd(res, ct):
        g, g_lse = ct if with_lse else (ct, None)
        return _flash_bwd(res, g, g_lse, causal=causal, bq=bq, bk=bk,
                          interpret=interpret, window=window)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention_lse(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True,
    q_offset=0, kv_offset=0,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Flash attention over q (B, T, H, D), k (B, T, Hkv, D) and v (B, T, Hkv,
    Dv), H a multiple of Hkv, returning (out (B, T, H, Dv), lse) with lse
    (B, H, Tq) float32. Offsets may be Python ints OR traced int32
    scalars (they ride scalar prefetch). `window=W` (causal, zero offsets):
    query i sees keys i − W < j ≤ i, through the banded kernels. `keep` (B,
    Tq, Tk) int8 or bool (no window, zero offsets): query i sees key j iff
    also keep[i, j], through the `flash_attention_sel_*` kernels. Raises
    ValueError when the shapes can't be blocked — use `can_flash` first."""
    flash, args = _plan_call(q, k, causal, q_offset, kv_offset,
                             block_q, block_k, interpret, with_lse=True,
                             window=window, keep=keep)
    return flash(args[0], q, k, v, *args[1:])


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True,
    q_offset=0, kv_offset=0,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,
) -> jax.Array:
    """Same contract as `ops.attention.full_attention` (output only; the
    cheaper backward — no lse cotangent input)."""
    flash, args = _plan_call(q, k, causal, q_offset, kv_offset,
                             block_q, block_k, interpret, with_lse=False,
                             window=window, keep=keep)
    return flash(args[0], q, k, v, *args[1:])


def _no_offset(offset) -> bool:
    return isinstance(offset, int) and offset == 0


def _effective_window(window, causal, q_offset, kv_offset, t_k):
    """`window` as the kernels take it: None for no window and for one that
    hides no key (W >= Tk: the causal call, kernel for kernel)."""
    if window is None:
        return None
    if window < 1:
        raise ValueError(f"window={window}: a query sees at least itself")
    if not causal:
        raise ValueError("a window is the lower bound of a CAUSAL mask")
    if not (_no_offset(q_offset) and _no_offset(kv_offset)):
        raise ValueError(
            "windowed flash attention is for unsharded attention: it takes "
            f"no offsets (q_offset={q_offset!r}, kv_offset={kv_offset!r})")
    return None if window >= t_k else int(window)


def _checked_keep(keep, q, k, window, q_offset, kv_offset):
    """`keep` as the kernels take it: (B, Tq, Tk) int8."""
    if window is not None or not (_no_offset(q_offset) and _no_offset(kv_offset)):
        raise ValueError(
            "a data mask is for unsharded attention without a window: it takes "
            f"neither (window={window!r}, q_offset={q_offset!r}, "
            f"kv_offset={kv_offset!r})")
    want = (q.shape[0], q.shape[1], k.shape[1])
    if keep.shape != want or keep.dtype not in (jnp.int8, jnp.bool_):
        raise ValueError(f"keep is {keep.dtype}{keep.shape}; the kernels take "
                         f"int8 or bool {want}: one plane a batch row, no head axis")
    return keep.astype(jnp.int8)


def _plan_call(q, k, causal, q_offset, kv_offset, block_q, block_k,
               interpret, with_lse, window=None, keep=None):
    """(the rule of this call's plan, its leading arguments: the offsets and,
    where the call has one, the data mask)."""
    interpret = kernel_interpret(interpret)
    if keep is not None:
        keep = _checked_keep(keep, q, k, window, q_offset, kv_offset)
    window = _effective_window(window, causal, q_offset, kv_offset, k.shape[1])
    blocks, fwd_blocks = (
        _plan_blocks(q.shape, k.shape, block_q, block_k, dtype=q.dtype,
                     keep=keep is not None, forward=forward)
        for forward in (False, True))
    if blocks is None:
        raise ValueError(
            f"flash_attention cannot block Tq={q.shape[1]}, Tk={k.shape[1]} "
            f"dtype={q.dtype} (need a power-of-two divisor >= "
            f"{_min_block(q.dtype)})")
    bq, bk = blocks
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])
    return (_make_flash(bool(causal), fwd_blocks, bq, bk, interpret, bool(with_lse),
                        window),
            (offs,) if keep is None else (offs, keep))


def kv_block_visits(t_q: int, t_k: int, window: Optional[int],
                    head_dim: int = _LANE, dtype=None) -> Tuple[int, int]:
    """((q block, kv block) pairs a head's forward COMPUTES under
    `window`, the pairs the causal call computes), at the blocks the call
    would plan: at 16 384 tokens and 1024-blocks 31 and 136 under a window of
    1024 (two blocks a q block, each half masked) and 45 and 136 under one of
    2048 (three: an edge half masked, a whole one, the diagonal — 67% of the
    computed pairs visible, where 1024 shows 50%). (0, 0) where the shapes
    cannot be blocked."""
    window = _effective_window(window, True, 0, 0, t_k)
    blocks = _plan_blocks((1, t_q, 1, head_dim), (1, t_k, 1, head_dim), None, None, dtype,
                          forward=True)
    if blocks is None:
        return 0, 0
    bq, bk = blocks
    # a window of every key is the causal band
    banded, causal = (_kv_band(t_q // bq, t_k // bk, bq, bk, w)[1]
                      for w in (window or t_q + t_k, t_q + t_k))
    return banded, causal


def _plan_blocks(q_shape: Tuple[int, ...], k_shape: Tuple[int, ...],
                 block_q: Optional[int], block_k: Optional[int],
                 dtype=None, keep: bool = False,
                 forward: bool = False) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for these shapes, or None: the backward's, or with
    `forward` the forward's. Targets not given are `DEFAULT_BLOCK_*`, with or
    without a window. The FORWARD takes them as they are, whatever the head
    and with or without a data mask (`keep`, whose int8 tiles also set the
    least block): it passes a VMEM limit of its own and holds no dk and dv.
    The BACKWARD takes q blocks of `SEL_BLOCK_Q` with a data mask, and at a
    head (q's and k's) wider than the lane width a key block smaller in
    proportion, so that a key block's rows times the head size stay what they
    are at 128 (at 192 the power of two under 682: 512): at head
    256 and (1024, 1024) the split dq kernel's blocks and scratch need 16.9 MB
    of the 16 MB it may use; T=8192, 20 heads, bfloat16 on a v5e, forward +
    backward at one plan for both: (1024, 512) 25.6 ms, (512, 1024) 25.6,
    (512, 512) 28.3, (256, 1024) 30.2 (PERF.md section 6, PR 32). The forward
    alone at that shape, resident / streaming ms a call (my chip run, PR 46,
    host clock over back-to-back calls): (1024, 1024) 4.84 / 5.89, (512,
    1024) 5.07 / 7.09, (2048, 1024) 5.23 / 5.68, (1024, 512) 5.63 / 6.56,
    (2048, 512) 5.88 / 6.30, (512, 512) 6.21 / 8.32 — the accumulator is
    rescaled once a key block; and with a data mask at 16 384 keys, 32 heads
    on 4 of 128: (1024, 1024) 17.90 / 22.32, (512, 2048) 18.16 / 25.77, (512,
    1024) 19.84 / 27.69, (256, 1024) 24.10 / 39.24."""
    block_q = block_q or (SEL_BLOCK_Q if keep and not forward else DEFAULT_BLOCK_Q)
    block_k = block_k or DEFAULT_BLOCK_K
    mb = max(_min_block(dtype), _min_block(jnp.int8)) if keep else _min_block(dtype)
    if q_shape[-1] > _LANE and not forward:
        block_k = max(mb, block_k * _LANE // q_shape[-1])
    bq = pick_block(q_shape[1], block_q, mb)
    bk = pick_block(k_shape[1], block_k, mb)
    if bq is None or bk is None:
        return None
    return bq, bk


def can_flash(q_shape: Tuple[int, ...], k_shape: Tuple[int, ...],
              q_offset=0, kv_offset=0, dtype=None,
              window: Optional[int] = None, keep: bool = False) -> bool:
    """True when flash_attention supports these shapes/dtype AND a backend
    that can run the Mosaic kernel is active: real TPU, or CPU inside
    `force_tpu_interpret_mode` (tests). EDL_FLASH=0 force-disables;
    EDL_FLASH=1 force-enables but ONLY on those backends — on plain CPU/GPU
    the kernel has no compile path, so forcing it there would crash rather
    than fall back. Offsets may be traced; without a window they are
    accepted for API symmetry and ignored. A `window` is DECLINED with any
    offset that is not the Python integer 0 (ring attention's are traced):
    the banded grids are for unsharded attention. A data mask (`keep`) is
    declined with any such offset too, and with a window."""
    offsets = not (_no_offset(q_offset) and _no_offset(kv_offset))
    if (window is not None or keep) and offsets or (keep and window is not None):
        return False
    flag = os.environ.get("EDL_FLASH", "")
    if flag == "0":
        return False
    if _plan_blocks(q_shape, k_shape, None, None, dtype=dtype, keep=keep) is None:
        return False
    runnable = jax.default_backend() == "tpu" or _interpret_active()
    if flag == "1":
        return runnable
    return jax.default_backend() == "tpu"
