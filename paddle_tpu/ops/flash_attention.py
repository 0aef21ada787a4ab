"""Flash attention — Pallas TPU kernel with streaming softmax.

The fused attention kernel the registry docstring promises: computes
softmax(QK^T * scale [+ causal mask]) V without materializing the [T, T]
score matrix in HBM. Forward keeps a running (max, denominator,
accumulator) per query block while streaming key/value blocks through
VMEM; backward recomputes probabilities from the saved log-sum-exp rows
in ONE kernel (flash_bwd), each tile's s, p, dp and ds computed once and
dq, dk, dv all made from them: where a block holds all of T (PR 31) and
where T is streamed, dq for all rows held in VMEM across the key blocks
(ISSUE 39), a score of two parts included (ISSUE 56). The standard
two-kernel dq / dk+dv scheme is left for a T whose dq VMEM cannot hold.

Reference capability: the reference's attention is composed matmul +
softmax ops (nets.py:168 scaled_dot_product_attention,
tests/unittests/transformer_model.py:41); SURVEY §7 marks attention as
the place where a hand kernel beats XLA fusion. Design follows
/opt/skills/guides/pallas_guide.md (grid + VMEM scratch carried across
the sequential k-block grid dimension; masks generated in-kernel with
broadcasted_iota).

Two sizes (PR 25). The MAJOR block is what a grid step holds in VMEM
and the DMA moves: all of T where a [T, D] operand is small there
(_auto_block: bf16 up to T 2048 at D <= 128, one grid step a block of
heads, nothing carried between steps and no scratch: each panel
finishes its own rows), else the largest divisor of T up to 1024,
streamed with the running statistics in scratch. A major block
below the diagonal is one batch of work; one ON the diagonal is cut, at
trace time, into PANELS of _TILE rows whose keys stop at the diagonal,
so that tiles above it are never computed (36 of 64 tiles of 256 at
T 2048) and only the tiles it crosses are masked (_walk).

Precision: MXU operands keep the dtype they arrive in (bf16 under AMP,
float32 otherwise), p and ds are cast to it before their matmuls, and
every dot accumulates in float32; the running max, the denominator,
lse, delta, exp and all accumulators are float32. `scale` is folded
into q (into k where the scores are held transposed: flash_bwd_dkv and
flash_bwd) once a panel; dq and dk are products with the unscaled
operand, scaled in float32 as they are stored.

Shapes (PR 29): q, k, v and the output are [B, T, H*D], the layout a
projection leaves them in, with a static n_head. A grid step's block
is (1, rows, W) of that last dimension, W lanes holding g = W / D whole
heads (heads_per_block: from D and H alone): one head where D is a
multiple of 128; 128 / D heads where D divides 128 and that many
divide H (two heads of 64 to a 128-lane block); else all of H*D where
a [rows, H*D] operand fits a block. Nothing is transposed or reshaped
on the way in or out. The g heads of a block take turns in a loop the
kernel runs (_each_head) and are separated without moving lanes: head
a's q (k and v in dk/dv) is the block with the other heads' lanes
zeroed, so s_a contracts all W lanes and the foreign ones add exact
zeros; every product that comes out W lanes wide keeps head a's D by
a lane select where it is stored (_only, _put). Under grouped
key/value heads a block of ONE head reads its group's key/value head
through the index map (_specs); where a block holds g > 1 heads and g
divides the group (two heads of 64 in groups of four: ISSUE 49), all
of its heads read the same key/value head, and _attend hands the
kernels k and v with each key/value head under each of its query heads'
lanes, [B, T, H*D], made before the custom_vjp so that autodiff folds
dk and dv back over the group: the kernels run as without groups. A
group that g does not divide is dense math. A [T, 64] operand
padded to 128 lanes in VMEM before, so two heads take the bytes one
took, and a contraction or an output of 64 half fills the MXU: the
passes are those of one head at a time. T must be a multiple of the block size
(the sp bucketing guarantees powers of two); D any multiple of 8. The
row statistics (lse, delta) are float32, one float a row and head, the
rows along the lanes: [B*H, 1, T], g heads' rows to a grid step.
`delta` = rowsum(dy * o) over each head's lanes is made inside a kernel
from the rows of dy it holds and o as one more operand: XLA, asked for
[B*H, 1, T] from [B, T, H*D] operands, first copies both whole into a
T-minor layout (every formulation compiled for a described v5e did;
PR 29). flash_bwd keeps it in VMEM; in the two kernels flash_bwd_dq
hands it to flash_bwd_dkv as a row statistic.

The backward (PR 31, ISSUE 39) follows from the shapes alone
(_backward_of; no flag), and is ONE kernel named flash_bwd wherever dq
can stay in VMEM: it walks by keys with the scores transposed and makes
dv, dk AND dq from one s, p, dp and ds a tile (five matmuls where the
two kernels run seven, one exp a score for two, one pass over q, k, v,
dy); delta never goes through HBM. "fused" where one block holds all of
T (bf16 up to T 2048, the cell opt350m_train, ring attention's shards
that short): PR 31's kernel as it was, grid (B * H / g,), dv and dk
finishing inside their panel and dq summed across panels in a float32
[T, W] scratch. (The streamed kernel run at one block is the same
device time alone and bit for bit the same gradients, but 0.18 ms a
step slower inside opt350m_train's step, 0.1% of its tokens/s, my chip
runs, PR 39: so PR 31's stays.) "fused_streamed" where T is streamed
(float32 above T 1024, T 4096 and longer, blocks wider than 128 lanes
above 512 rows, the own-block form's halves: the cells sdar_train_bd4k
and trinity_train_T16k, a layer's backward 10.9 ms for the two kernels'
15.6 and 36.7 for 51.2, my chip runs, PR 39): _bwd_one_kernel on
flash_bwd_dkv's grid (B * H / g, nK, steps_q), dq for ALL rows of the
block of heads in float32 scratch that stays in VMEM over both inner
axes, scaled, cast and stored once, at the block of heads' last step;
delta is made at a q block's first visit and kept in scratch. It asks
for the scoped VMEM its shapes need (_one_kernel_vmem_bytes: at
T 16,384 dq is 8 MB and its output block 4 MB twice, which the 16 MB
default would not hold).
"two_kernels", flash_bwd_dq then flash_bwd_dkv, each recomputing s and
dp, where what would stay resident passes _RESIDENT_DQ_BYTES (T above
32,768 at one head of 128 in bf16: ring attention's longest shards; a
score of two parts keeps a pair of heads' dq and dq2, so T above 8,192
at a second part of 64 in bf16).

Dispatch: `flash_bthd(q, k, v, n_head, causal, scale)` uses the kernel
on TPU and the dense jnp math elsewhere (CPU tests exercise the kernel
via interpret mode separately); `flash_attention` / `flash_attention_lse`
take [B, H, T, D] and are wrappers round it that transpose in and out:
such a caller (parallel/ring.py) now pays the transposes the model used
to pay. Each dispatch counts itself at trace time in
`ptpu_flash_lowerings_total{path, entry, heads_per_block, backward,
mask, kv_groups, key_width, value_width, second_part}` (backward:
"fused" / "fused_streamed" / "two_kernels", "none" on the dense path).

A score of two parts (PR 34): `flash_bthd(..., q2=, k2=)` adds
`q2_h k2^T` to head h's scores, k2 ONE key [B, T, D2] that every head
reads (latent attention's rotary part): key D + D2 wide, value D. The
streamed forward, the ONE streamed backward kernel (ISSUE 56: eight
matmuls a tile where the two kernels run eleven) and the two backward
kernels take it on what they are handed (`part2`; see "A score of two
parts" below); handed no second part they trace exactly what they
traced before.

The own-block mask form (PR 37): `flash_bthd(..., causal=True,
mask_block=m, own_block=True)` is block diffusion's whole attention.
The rows are two halves at the same positions, [noised; clean]; the
grid's q axis runs through both, the keys walked are the clean half's
(block offsets in the index maps: nothing is sliced out of q, k or v
and the result is written at the halves' places), and ON the diagonal
a noised panel takes one segment more, the noised keys of its own
rows, kept where `kj >> shift == qi >> shift`, inside the same
streaming-softmax step (_walk, _causal). The mask is a static value;
under the other forms every kernel traces what it traced before.

A window bound (ISSUE 38): `flash_bthd(..., causal=True, window=w)`
keeps, for query i, the keys j with `i - w < j <= i`: a band under the
diagonal. The streamed kernels (and the fused backward, where a
block holds all of T) take it as a fourth mask form, (0, _WIN, w). What
it saves is what is NOT walked: the grid's key axis (the q axis where
the walk is by keys: flash_bwd, flash_bwd_dkv) has only the band's
steps, `ceil((w - 1) / block) + 1`,
and the index maps start it at the q block's own place, so a key block
wholly under the band is neither fetched nor computed; a block wholly
inside it is one unmasked batch of work; the blocks that the diagonal
or the band's lower edge cross are cut, at trace time, into panels
whose tiles outside the band are left out and whose crossed tiles alone
are masked (_band_cuts). It composes with grouped key/value heads and
with an lse output, and with nothing else: a mask in blocks, `strict`,
`own_block` or a second score part beside it raise. `window >= T` is
plain causal and takes causal's path. Each lowering that walks a band
adds, to `ptpu_flash_band_scores_total{window, walk, kind}`, the scores
its walks compute and the scores the band holds, both static.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..monitor import metrics as _metrics

_NEG_INF = -1e30
_LANES = 128
# None → auto block sizing in _resolve_path (_auto_block). Measured on
# one TPU v5e at the benchmark's shape, q/k/v/dy bf16 [4, 16, 2048, 64],
# causal, ms a call for flash_fwd / flash_bwd_dq / flash_bwd_dkv (my chip
# runs, PR 25; PERF.md section 6 has every row):
#   the parent, 1024^2 blocks, every score masked     0.841  1.020  1.377
#   the same with bf16 MXU operands, p / ds cast      0.840  1.051  1.329
#   the same with 512^2 blocks as grid steps          1.260  1.183  1.536
#   the same with 256^2 blocks as grid steps          2.380  2.151  3.308
#   256^2 tiles walked by lax.fori_loop in the kernel 2.550  1.394  1.848
#   this file: one block, panels of 256 cut statically 0.536  0.693  0.888
# So neither the MXU's passes nor a grid step's hand-over sets the pace:
# small tiles lose because each is a chain of matmul, reduction, exp and
# matmul that nothing overlaps, as a grid step or as a loop iteration
# alike. Straight-line panels give the scheduler the whole block at once.
# Panels of 128 to 512 rows are within 2% of one another (256: least
# masked work without the smallest matmuls); D 128 prefers the same.
# PR 29, the same call from q/k/v/dy [4, 2048, 1024] as the projections
# leave them; last column: every device op round the kernels (my chip
# runs, PR 29):
#   the parent: XLA splits and merges the heads       0.536  0.692  0.889  0.491
#   two heads to a block, unrolled at trace time      0.495  0.627  0.810  0
#   this file: two heads to a block, taking turns     0.518  0.637  0.812  0
# No scratch where nothing is carried, and a panel's dead branch (below
# the diagonal, in a grid of one block) not compiled: faster than the
# parent's kernels although dq now also makes delta. Unrolled, the
# scheduler overlaps the two heads (1.8% faster) for 2.6 times the
# equations to trace and lower: 8 s more in every set-up.
# PR 31, the same call; flash_fwd 0.5175 in every row, then the backward
# (my chip runs, PR 31):
#   the parent: flash_bwd_dq + flash_bwd_dkv          0.6371 + 0.8122 = 1.4493
#   one kernel by keys, dq += dot_general(dst, k) contracting dim 0 of
#     both, or dst.T in bf16 then a plain matmul (the same lowering)  1.0934
#   the same, dq^T = k^T dst accumulated [W, T], turned once a head  1.0879
#   this file: dst turned round in float32, then cast                1.0786
#   the same with the dq product left out (no candidate: the floor)  0.8318
# Four matmuls cost 0.208 each, so five cannot go under 1.04; the one
# that wants ds in the other orientation costs 0.247 with its T^2-sized
# transposes: they hide behind the MXU all but 0.04 ms.
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None
_AUTO_BLOCK = 1024              # streamed major block, rows
_ONE_BLOCK_BYTES = 512 * 1024   # a [T, D] operand in VMEM this small: one block
# dq of every row of a block of heads, float32, plus its output block
# twice: what the one backward kernel may keep in VMEM from a block of
# heads' first step to its last. A quarter of a v5e core's 128 MiB:
# T 16,384 at one head of 128 in bf16 is 16 MiB, T 32,768 the last that
# fits; longer (ring attention's longest shards) takes the two kernels.
# A score of two parts keeps a pair of heads' dq and their dq2: 24 MiB at
# T 8,192 with a second part of 64 in bf16, the last that fits.
_RESIDENT_DQ_BYTES = 32 * 1024 * 1024
_TILE = 256                     # a panel's rows on the diagonal
_PANEL_SCORES = 1024 * 1024     # an unmasked panel's scores (4 MB in float32)


def _dense(q, k, v, causal, scale):
    return _dense_lse(q, k, v, causal, scale)[0]


def _dense_lse(q, k, v, causal, scale, mask=(0, 0), q2=None, k2=None):
    """Dense math returning (out, lse) — lse[b,h,i] = logsumexp_j s_ij.
    The math-identical fallback for flash_attention_lse. k and v may
    hold fewer heads than q (query head h reads head h // group);
    `mask` = (shift, form) is _causal's, read where `causal`; under the
    form _OWN the T rows are [noised; clean] halves, and (0, _WIN, w)
    keeps the w keys up to the query's own. q2
    [B, H, T, D2] and k2 [B, T, D2], where given, add a second part to
    every score: q2 against the ONE key k2 that all heads read."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    if q2 is not None:
        s = s + jnp.einsum("bhqd,bkd->bhqk", q2, k2,
                           preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        t = s.shape[-1]
        shift, strict = mask[:2]
        if strict == _OWN:
            # a clean key counts for its own half from its own block on
            # and for the noised half from the block after; a noised key
            # for the noised rows of its block alone
            at = jnp.tile(jnp.arange(t // 2) >> shift, 2)
            noised = jnp.arange(t) < t // 2
            seen = jnp.where(
                noised[None, :],
                noised[:, None] & (at[None, :] == at[:, None]),
                at[None, :] + noised[:, None] <= at[:, None])
        elif strict == _WIN:
            ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
            seen = (ahead >= 0) & (ahead < mask[2])
        else:
            at = jnp.arange(t) >> shift
            seen = at[None, :] + strict <= at[:, None]
        s = jnp.where(seen, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / l,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, (m + jnp.log(l))[..., 0]


# --------------------------------------------------------------------------
# What the three kernels share. A grid step holds one MAJOR block of q
# rows and one of keys in VMEM (what the DMA moves). Below the diagonal it
# is one batch of work; on the diagonal it is cut into panels, so that the
# tiles above the diagonal are never computed and only the tiles the
# diagonal crosses pay for a mask. The cut is static (Python, at trace
# time): a loop inside the kernel would serialise matmul, reduction and
# exp of each small tile, and measured slower than the masked work it
# saves (PERF.md section 6, PR 25).
_NT = (((1,), (1,)), ((), ()))     # a @ b^T: both contract their last dim
_NN = (((1,), (0,)), ((), ()))     # a @ b


def _dot(a, b, dims):
    """MXU operands in the dtype they arrive in, float32 accumulation."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _tile(block, target):
    """Edge of the panels a major block on the diagonal is cut into: the
    largest multiple of 128 up to `target` that divides it; a block
    with no such divisor is one panel."""
    for t in range(min(target, block) // _LANES * _LANES, 0, -_LANES):
        if block % t == 0:
            return t
    return block


_OWN = 2       # the third mask form: see _causal
_WIN = 3       # the fourth: (0, _WIN, window), a band under the diagonal


def _causal(s, off, q_axis, mask=(0, 0)):
    """Mask one score tile. `off` = its first query row less its first
    key; queries run along `q_axis` of the tile, keys along the other.
    `mask` = (shift, form): rows and keys are counted in blocks of
    2^shift, a query sees the keys of its own block and of those before
    it, and form 1 (`strict`) takes its own block away (a first block's
    rows then see nothing: their scores are all _NEG_INF, which is
    finite, so out and lse come out finite, and lse = -1e30 weighs
    nothing in a merge). (0, 0) is plain causal. Tiles start on a
    multiple of the block, so `off >> shift` is their distance in
    blocks. Form _OWN keeps the keys of the query's own block and no
    others: the tile a noised row of block diffusion makes with the
    noised keys of its own rows (`off` 0), beside the strict tile it
    makes with the clean ones (_walk). (0, _WIN, w) keeps a query's own
    key and the w - 1 before it: both edges of the band, for a tile
    that the lower one crosses."""
    shift, strict = mask[:2]
    qi = lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kj = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if strict == _OWN:
        return jnp.where((kj >> shift) == (qi >> shift), s, _NEG_INF)
    if strict == _WIN:
        ahead = off - (kj - qi)     # the query's row less the key's
        return jnp.where((ahead >= 0) & (ahead < mask[2]), s, _NEG_INF)
    if not shift and not strict:
        return jnp.where(kj - qi <= off, s, _NEG_INF)
    return jnp.where((kj >> shift) - (qi >> shift) + strict
                     <= (off >> shift), s, _NEG_INF)


def _own(mask):
    """Whether a mask (a kernel's, or a segment's) is the own-block form."""
    return mask is not None and mask[1] == _OWN


def _band(mask):
    """Whether a mask is the window form, (0, _WIN, window)."""
    return mask is not None and mask[1] == _WIN


def _band_steps(window, block, blocks):
    """The major blocks a q block's band touches (a key block's, by
    keys): its own and those the `window - 1` keys before its first row
    reach into, of the `blocks` there are."""
    return min(-(-(window - 1) // block) + 1, blocks)


def _band_cuts(delta, block, tile, window, by_keys=False):
    """The static cut of the major block pair whose q block lies `delta`
    (equal) blocks after its key block, under a window: None where the
    whole block is inside the band (_walk's `whole`), else a list of
    _walk's (mine, segments), one a panel of `tile` rows (of keys,
    `by_keys`; a block no larger than a tile is one panel). A tile above
    the diagonal or wholly under the band is left out, runs of tiles
    wholly inside the band are one unmasked segment, a tile that the
    diagonal alone crosses is masked as causal's, and one that the
    lower edge crosses by both edges."""
    if delta and window >= (delta + 1) * block:
        return None
    tile = min(tile, block)
    n = block // tile

    def kind(r, c):
        qt, kt = (c, r) if by_keys else (r, c)
        q0, k0 = delta * block + qt * tile, kt * tile
        q1, k1 = q0 + tile - 1, k0 + tile - 1
        if k0 > q1 or k1 <= q0 - window:
            return None
        if k0 > q1 - window:        # the lower edge passes under it
            return "whole" if k1 <= q0 else "causal"
        return "band"

    cuts = []
    for r in range(n):
        segments, c = [], 0
        while c < n:
            what, first = kind(r, c), c
            while c < n and kind(r, c) == what:
                c += 1
            if what:
                q0, k0 = (first, r) if by_keys else (r, first)
                segments.append((
                    slice(first * tile, c * tile),
                    None if what == "whole"
                    else delta * block + (q0 - k0) * tile,
                    {"whole": None, "causal": (0, 0),
                     "band": (0, _WIN, window)}[what]))
        if segments:
            cuts.append((slice(r * tile, (r + 1) * tile), segments))
    return cuts


def band_scores(t, block, tile, window, by_keys=False):
    """(scores the band's walk computes, scores the band holds) for one
    head's [t, t]: what _walk does under (0, _WIN, window) in equal
    major blocks of `block` rows, counted from the same cuts."""
    blocks = t // block
    computed = 0
    for delta in range(_band_steps(window, block, blocks)):
        cuts = _band_cuts(delta, block, tile, window, by_keys)
        computed += (blocks - delta) * (block * block if cuts is None else sum(
            (mine.stop - mine.start) * (cols.stop - cols.start)
            for mine, segments in cuts for cols, _, _ in segments))
    seen = min(window, t)       # the first rows see fewer than a window
    return computed, seen * (seen + 1) // 2 + (t - seen) * seen


def _when(cond):
    """pl.when, settled at trace time where the condition is."""
    if isinstance(cond, bool):
        return lambda f: f() if cond else None
    return pl.when(cond)


def _block_ids(nq, nk, by_keys=False):
    """(i, j) of the grid step's q block and key block: program ids, or
    the integer 0 on an axis of one block, so that _walk settles at
    trace time which side of the diagonal the block is on and the other
    side's panels are neither traced nor compiled."""
    qa, ka = (2, 1) if by_keys else (1, 2)
    # hoisted by the kernels: program_id inside a pl.when branch does not
    # interpret/lower on all paths
    return (pl.program_id(qa) if nq > 1 else 0,
            pl.program_id(ka) if nk > 1 else 0)


def _unfold(at, outer, inner):
    """(at // inner, at % inner) of a grid axis that folds two, settled
    at trace time where one of them has a single step."""
    if inner == 1:
        return (at if outer > 1 else 0), 0
    if outer == 1:
        return 0, at
    return at // inner, at % inner


def _walk(panel, i, j, mask, block_q, block_k, tile, by_keys=False, half=0,
          band=None):
    """Run `panel(mine, segments)` over the major block (i, j).

    `mine` is a static slice of the block's query rows (of its keys if
    `by_keys`: dk/dv accumulates by key) and `segments` a list of
    (static slice of the other side, off, how): off None = every score
    of the segment counts, else the segment is masked by
    `_causal(s, off, ., how)`, off its first query row less its first
    key. A block below the diagonal is one unmasked segment, in panels
    of at most _PANEL_SCORES scores (a panel's scores are live in VMEM,
    several float32 copies of them); a block above it is skipped.
    Equal blocks cross the diagonal only ON it, where the cut is known
    at trace time: panel r holds rows [r t, (r+1) t) with the keys
    before them unmasked and their own keys masked (the transpose of
    that by keys). Unequal blocks cross it anywhere: one masked panel.
    `mask` is None (every score counts) or _causal's (shift, form): a
    mask in blocks of 2^shift no larger than a tile moves no tile from
    one side of the diagonal to the other, so the walk is causal's.

    Under the form _OWN (block diffusion) the q blocks are two halves of
    `half` blocks each, [noised; clean], `i` counts through both, and
    the keys walked are the CLEAN half's (equal blocks). Both halves
    walk as causal does; ON the diagonal the clean rows' own tile is
    masked block-causal and the noised rows' strict, and a noised
    panel takes one segment more: the NOISED keys of its own rows, kept
    where they are of the row's block (how = (shift, _OWN); the kernel
    reads them from its second pair of key/value blocks). By keys that
    segment is a panel of its own, since it ends in the noised keys' dk
    and dv and not in the clean ones'.

    Under the window form (0, _WIN, w), in equal blocks, the grid's key
    axis holds only the band's steps: `band` = (steps, blocks of the
    walked side in all). `j` is then the step, and the key block lies
    `steps - 1 - j` blocks before q block `i` (a step that would start
    before the first key block does nothing); by keys `i` is the step
    and the q block lies `i` blocks after key block `j`. Each distance
    is static inside its branch: a block wholly inside the band runs as
    `whole`, one that the diagonal or the lower edge crosses by its
    static cut (_band_cuts).
    """
    nq, nk = (block_k, block_q) if by_keys else (block_q, block_k)

    def whole():
        step = _tile(nq, max(_PANEL_SCORES // nk, _LANES))
        for r in range(nq // step):
            panel(slice(r * step, (r + 1) * step),
                  [(slice(0, nk), None, None)])

    def crossed(off, how, own=None):
        if block_q != block_k or tile >= block_q:
            cuts = [(slice(0, nq), slice(0, nk), slice(0, 0), off)]
        else:
            cuts = []
            for r in range(block_q // tile):
                mine = slice(r * tile, (r + 1) * tile)
                cuts.append((mine, mine, slice((r + 1) * tile, block_q)
                             if by_keys else slice(0, r * tile), 0))
        for mine, other, rest, at in cuts:
            segments = [(other, at, how)] + (
                [(rest, None, None)] if rest.stop > rest.start else [])
            if own and by_keys:
                panel(mine, [(mine, 0, own)])
            elif own:
                segments.append((mine, 0, own))
            panel(mine, segments)

    if mask is None:
        return whole()
    if _band(mask):
        steps, blocks = band or (1, 1)
        inside = []
        for delta in range(steps):
            here = (i == delta) & (j + delta < blocks) if by_keys \
                else (j == steps - 1 - delta) & (i >= delta)
            cuts = _band_cuts(delta, block_q, tile, mask[2], by_keys)
            if cuts is None:
                inside.append(here)
                continue

            def crossed_band(cuts=cuts):
                for cut in cuts:
                    panel(*cut)
            _when(here)(crossed_band)
        if inside:
            _when(functools.reduce(lambda a, b: a | b, inside))(whole)
        return
    if _own(mask):
        noised = i < half
        i = jnp.where(noised, i, i - half)
        pl.when(i > j)(whole)
        pl.when((i == j) & noised)(
            lambda: crossed(0, (mask[0], 1), own=mask))
        pl.when((i == j) & jnp.logical_not(noised))(
            lambda: crossed(0, (mask[0], 0)))
        return
    first_q, first_k = i * block_q, j * block_k
    # the last key every row of the block sees unmasked, plus one where
    # a row does not see its own key
    last_k = first_k + block_k - 1 + mask[1]
    _when(first_q >= last_k)(whole)
    _when((first_q < last_k) & (first_q + block_q - 1 >= first_k))(
        lambda: crossed(first_q - first_k, mask))


# --------------------------------------------------------------------------
# g heads to a block. Operands stay [B, T, H*D]; a block is W = g * D of
# the last dimension's lanes. Head a's operand is the block with the
# other heads' lanes zeroed (a contraction over all W lanes then adds
# exact zeros), and head a's part of a W-wide product is kept by a lane
# select as it is stored. The heads of a block take turns in a loop the
# KERNEL runs: unrolled at trace time, two heads' panels were twice the
# equations to trace and lower in every set-up (8 s more of a 41 s warm
# start, my chip run, PR 29) and twice the VMEM. g == 1 (D a multiple of
# 128) loops over nothing and selects nothing.
def heads_per_block(n_head, d):
    """Heads to a block of the last dimension, from D and H alone."""
    if d % _LANES == 0:
        return 1
    if _LANES % d == 0 and n_head % (_LANES // d) == 0:
        return _LANES // d
    return n_head                   # all of H*D as one block


def _each_head(g, head):
    """head(a) for each head a of the block, one after the other."""
    if g == 1:
        return head(0)

    def turn(a, carry):
        head(a)
        return carry
    lax.fori_loop(0, g, turn, 0)


def _lanes(shape, a, d, g):
    """Where head `a`'s lanes are in a [rows, g * d] tile; None where the
    block is one head."""
    if g == 1:
        return None
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= a * d) & (lane < (a + 1) * d)


def _only(x, mine):
    """x with the other heads' lanes zeroed."""
    return x if mine is None else jnp.where(mine, x, jnp.zeros_like(x))


def _put(ref, idx, x, mine):
    """Store x's lanes of this head, the other heads' as they are."""
    ref[idx] = x if mine is None else jnp.where(mine, x, ref[idx])


# --------------------------------------------------------------------------
# forward kernel: grid (B * H / g, nQ, nK); scratch (m, l, acc) carried
# across the (sequential, innermost) nK dimension. One key block carries
# nothing: each panel finishes its own rows, and there is no scratch.
def _fwd_kernel(*refs, mask, scale, block_q, block_k, tile, nq, nk, d, g,
                part2=None, band=None):
    if part2:
        q_ref, k_ref, v_ref, q2_ref, k2_ref, o_ref, lse_ref, *scratch = refs
        a2 = pl.program_id(0) % part2[1]    # its place in q2's block
    elif _own(mask):
        # nq counts both halves' q blocks; kn / vn are the noised keys
        # and values of the q block's own rows
        (q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref, lse_ref,
         *scratch) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch = refs
    i, j = _block_ids(nq, nk)
    if nk > 1:
        m_s, l_s, acc_s = scratch

        @pl.when(j == 0)
        def _init():
            m_s[:] = jnp.full_like(m_s, _NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

    def head(a):
        def finish(rows, mine, m, l, acc):
            l = jnp.maximum(l, 1e-30)
            _put(o_ref, (0, rows, slice(None)),
                 (acc / l).astype(o_ref.dtype), mine)
            lse_ref[a, :, rows] = (m + jnp.log(l)).T    # [tq, 1] -> row

        def panel(rows, segments):
            # ONE streaming-softmax step of these rows over all their
            # segments: one running-max update however the keys are cut
            q = q_ref[0, rows, :] * scale           # [tq, W], once a panel
            mine = _lanes(q.shape, a, d, g)
            q = _only(q, mine)
            if part2:
                q2 = q2_ref[0, rows, :] * scale
                q2 = _only(q2, _lanes(q2.shape, a2, *part2[:2]))
            scores = []
            for cols, off, how in segments:
                k = (kn_ref if _own(how) else k_ref)[0, cols, :]
                s = _dot(q, k, _NT)                     # [tq, tk]
                if part2:
                    s = s + _dot(q2, k2_ref[0, cols, :], _NT)
                scores.append(s if off is None else _causal(s, off, 0, how))
            maxes = [jnp.max(s, axis=1, keepdims=True) for s in scores]
            if nk == 1:
                # the only key block: nothing carried in, nothing to
                # rescale
                m_new = functools.reduce(jnp.maximum, maxes)
                l = acc = 0.0
            else:
                m_prev = m_s[a, rows]                # [tq, 1]
                m_new = functools.reduce(jnp.maximum, maxes, m_prev)
                alpha = jnp.exp(m_prev - m_new)
                l = alpha * l_s[a, rows]
                acc = alpha * acc_s[rows]
            for s, (cols, _, how) in zip(scores, segments):
                p = jnp.exp(s - m_new)
                v = (vn_ref if _own(how) else v_ref)[0, cols, :]
                l = l + jnp.sum(p, axis=1, keepdims=True)
                acc = acc + _dot(p.astype(v.dtype), v, _NN)  # [tq, W]
            if nk == 1:
                return finish(rows, mine, m_new, l, acc)
            m_s[a, rows] = m_new
            l_s[a, rows] = l
            _put(acc_s, rows, acc, mine)

        _walk(panel, i, j, mask, block_q, block_k, tile, half=nq // 2,
              band=band)

        if nk > 1:
            @pl.when(j == nk - 1)
            def _final():
                acc = acc_s[:]
                finish(slice(None), _lanes(acc.shape, a, d, g), m_s[a],
                       l_s[a], acc)

    _each_head(g, head)


def _specs(n_head, g, d, group=1):
    """Block specs of a grid (B * H / g, ., .): `rows(block, axis)` for a
    [B, T, H*D] operand, `block` rows a grid step following grid axis
    `axis` (dk/dv's grid puts the keys first; a function of the step
    where the block is not the axis' own: the own-block form's halves)
    and the g heads of the
    step on the last dimension; `kv(block, axis)` the same for k and v
    [B, T, (H / group) * D], which `group` query heads read (one head
    to a block: the step's head over `group`); `stat(block, axis)` for
    a row statistic [B*H, 1, T], one float a query row and head, the
    rows along the lanes."""
    hb = n_head // g

    def at(axis, s):
        return 0 if axis is None else axis(s) if callable(axis) else s[axis]

    def rows(block, axis=None):
        return pl.BlockSpec(
            (1, block, g * d),
            lambda *s: (s[0] // hb, at(axis, s), s[0] % hb))

    def kv(block, axis=None):
        return pl.BlockSpec(
            (1, block, g * d),
            lambda *s: (s[0] // hb, at(axis, s), s[0] % hb // group))

    def stat(block, axis=None):
        return pl.BlockSpec((g, 1, block), lambda *s: (s[0], 0, at(axis, s)))

    return rows, (rows if group == 1 else kv), stat


# jitted so that a stack of layers traces and lowers each kernel ONCE:
# the panels make a kernel's body some hundreds of equations, and without
# the jit's cache every call site pays for them again (24 layers: 29 s
# more trace and lowering in the benchmark's set-up; my chip run, PR 25)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fwd_pallas(q, k, v, n_head, n_kv_head, mask, scale, block_q, block_k,
                interpret):
    b, t, hd = q.shape
    d = hd // n_head
    g = heads_per_block(n_head, d)
    rows, kv, stat = _specs(n_head, g, d, n_head // n_kv_head)
    if _own(mask):
        # q blocks through both halves; the keys walked are the clean
        # half's, `half` blocks on; the noised keys of a noised q
        # block's own rows ride along the q axis (the clean q blocks
        # keep the last of them: nothing is fetched for them)
        bq = bk = min(block_q, t // 2)
        half = t // 2 // bq
        nq, nk = 2 * half, half
        keys = [kv(bk, lambda s: s[2] + half)] * 2 + [
            kv(bq, lambda s: jnp.minimum(s[1], half - 1))] * 2
        operands = (q, k, v, k, v)
    else:
        bq = min(block_q, t)
        bk = min(block_k, t)
        nq, nk = t // bq, t // bk
        keys = [kv(bk, 2), kv(bk, 2)]
        operands = (q, k, v)
    band = None
    if _band(mask):
        # the key axis holds the band's steps alone, and step s[2] of q
        # block s[1] is the key block that many short of the last step
        # before it (block 0 again where that would be before the first:
        # nothing is fetched for a step that does nothing)
        nk = _band_steps(mask[2], bk, nk)
        band = (nk, nq)
        keys = [kv(bk, lambda s: jnp.maximum(s[1] - (nk - 1) + s[2], 0))] * 2
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, mask=mask, scale=scale,
                          block_q=bq, block_k=bk,
                          tile=_tile(bq, _TILE), nq=nq, nk=nk, d=d, g=g,
                          band=band),
        grid=(b * n_head // g, nq, nk),
        in_specs=[rows(bq, 1)] + keys,
        out_specs=[rows(bq, 1), stat(bq, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype),
            # one float a row and head, the rows along the lanes: what
            # the backward reads back as it is, and 1/128 of a lane tile
            # a row
            jax.ShapeDtypeStruct((b * n_head, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((bq, g * d), jnp.float32),
        ] if nk > 1 else [],
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out, lse.reshape(b, n_head, t)


# --------------------------------------------------------------------------
# backward kernels. p is recomputed per tile from the saved LSE, and
# delta = rowsum(dy * o) over each head's lanes [- dlse] is made INSIDE a
# kernel from the rows of dy and o it holds anyway (float32, as XLA
# summed it: but XLA wants T minor for a [B*H, 1, T] result and copies dy
# and o whole into that layout first). Both statistics are rows [1, T] a
# head: a kernel that holds the scores transposed broadcasts them down
# the sublanes as they are.
#
# Which backward runs follows from the shapes alone (_backward_of). ONE
# kernel, flash_bwd, wherever dq for all rows of a block of heads can
# stay in VMEM: a panel's s, p, dp and ds are computed once and dq, dk
# and dv all come out of them, five matmuls a tile: _bwd_fused_kernel
# where one block holds all of T, _bwd_one_kernel where T is streamed
# (and for every T of a score of two parts). Beyond its byte bound the
# two kernels: flash_bwd_dq walks a q block's keys and flash_bwd_dkv a
# key block's queries, each recomputing s and dp (seven matmuls for five
# useful, eleven for eight under two parts, two exp a score for one).
def _delta(dy_ref, o_ref, dlse_ref, a, d, g):
    """Column [rows, 1] of head `a`'s delta for the block's rows, and
    where the head's lanes are in a [rows, W] tile. The lse output's
    cotangent folds in: d lse_i / d s_ij = p_ij, so
    ds = p * (dp - delta') with delta' = delta - dlse."""
    dy = dy_ref[0]
    mine = _lanes(dy.shape, a, d, g)
    delta = jnp.sum(_only(dy, mine).astype(jnp.float32)
                    * o_ref[0].astype(jnp.float32), axis=1, keepdims=True)
    if dlse_ref is not None:
        delta = delta - dlse_ref[a].T
    return delta, mine


def _bwd_fused_kernel(*refs, mask, scale, t, tile, d, g, has_dlse):
    """grid (B * H / g,): all of T, one block of heads. Walks by keys with
    the scores transposed [tk, tq], as flash_bwd_dkv does, so that
    dv = p^T dy and dk = ds^T q are plain matmuls that finish inside
    their panel; the one product that wants the other orientation,
    dq[rows] += ds k, takes ds^T turned round in float32 on its way to
    the MXU (the transpose hides behind the matmuls: the product costs
    what a plain one does, PERF.md section 6, PR 31) and accumulates
    across panels in float32 scratch. delta never leaves VMEM."""
    q_ref, k_ref, v_ref, dy_ref, o_ref, lse_ref = refs[:6]
    dlse_ref = refs[6] if has_dlse else None
    dq_ref, dk_ref, dv_ref, dq_s, delta_s = refs[6 + has_dlse:]

    def head(a):
        delta, all_mine = _delta(dy_ref, o_ref, dlse_ref, a, d, g)
        delta_s[...] = delta.T                       # [T, 1] -> [1, T]
        dq_s[...] = jnp.zeros_like(dq_s)

        def panel(cols, segments):
            k = k_ref[0, cols, :]
            mine = _lanes(k.shape, a, d, g)
            kk, v = _only(k * scale, mine), _only(v_ref[0, cols, :], mine)
            dk = dv = 0.0
            for rows, off, how in segments:
                q = q_ref[0, rows, :]
                dy = dy_ref[0, rows, :]
                st = _dot(kk, q, _NT)                # [tk, tq]
                if off is not None:
                    st = _causal(st, off, 1, how)
                pt = jnp.exp(st - lse_ref[a, :, rows])   # row [1, tq]
                dv = dv + _dot(pt.astype(dy.dtype), dy, _NN)
                dst = pt * (_dot(v, dy, _NT) - delta_s[:, rows])
                dk = dk + _dot(dst.astype(q.dtype), q, _NN)
                # the other heads' lanes of k give lanes of dq that the
                # last store drops
                dq_s[rows] = dq_s[rows] + _dot(dst.T.astype(k.dtype), k, _NN)
            whole = (0, cols, slice(None))
            _put(dk_ref, whole, (dk * scale).astype(dk_ref.dtype), mine)
            _put(dv_ref, whole, dv.astype(dv_ref.dtype), mine)

        _walk(panel, 0, 0, mask, t, t, tile, by_keys=True)
        _put(dq_ref, (0, slice(None), slice(None)),
             (dq_s[...] * scale).astype(dq_ref.dtype), all_mine)

    _each_head(g, head)


def _bwd_one_kernel(*refs, mask, scale, block_q, block_k, tile, nq, nk,
                    blocks_q, d, g, has_dlse, band=None, part2=None):
    """flash_bwd, grid (B * H / g, nK, steps_q), the q blocks innermost:
    one block of heads. Walks by keys with the scores transposed
    [tk, tq], as flash_bwd_dkv does, so that dv = p^T dy and dk = ds^T q
    are plain matmuls; the one product that wants the other orientation,
    dq[rows] += ds k, takes ds^T turned round in float32 on its way to
    the MXU (the transpose hides behind the matmuls: the product costs
    what a plain one does, PERF.md section 6, PR 31) and accumulates in
    a float32 scratch [q blocks, rows of a block, W] that holds every
    row of the sequence and stays in VMEM over both inner grid axes.
    dq's output block is all of T (its index map ignores the inner
    axes) and is scaled, cast and stored at a block of heads' last
    step. delta is made at a q block's FIRST visit (key block 0; under a
    band also the last step of every later key block), where its slot
    of dq is zeroed too, and kept for all rows in scratch: it never goes
    through HBM. dk and dv are summed over a key block's q blocks in
    float32 scratch; where a key block has ONE q block (all of the
    queries in a block, the keys in several) they finish inside their
    panel and there is no such scratch. `nq` is what the inner axis
    counts (the band's steps under a window), `blocks_q` the q blocks
    there are.

    A score of two parts (`part2` = (D2, g2, H); one head to a block,
    see "A score of two parts" below): grid (B * H / g2, nK, g2 * nQ),
    the g2 heads that share a 128-lane block of q2 taking turns under
    one key block, each walking its q blocks. st takes `kk2 q2^T` on
    top, k2 being the shared key under THIS head's lanes of the block
    alone, so that q2's other lanes meet zeros, `dq2 += dst^T k2` comes
    out in this head's lanes of a float32 scratch that all g2 heads add
    into (resident as dq is, stored with it), and `dk2 += dst q2` keeps
    them by a lane select. dq and delta have a slot a head of the pair;
    dq's output block holds the pair's g2 * D lanes. dk2, the sum over
    ALL heads of one key, cannot stay resident with the heads on the
    outer axis: it is summed over the pair's steps in float32 scratch
    and written as the pair's float32 partial, which _bwd_pallas2 adds
    up after the kernel."""
    q_ref, k_ref, v_ref, dy_ref, o_ref, lse_ref = refs[:6]
    dlse_ref = refs[6] if has_dlse else None
    if part2:
        (q2_ref, k2_ref, dq_ref, dk_ref, dv_ref, dq2_ref, dk2_ref,
         *scratch) = refs[6 + has_dlse:]
        *scratch, dq2_s, dk2_s = scratch
    elif _own(mask):
        # as in flash_bwd_dkv: the noised keys and values of the key
        # block's rows, and their gradients, whole at the step i == j
        (kn_ref, vn_ref, dq_ref, dk_ref, dv_ref, dkn_ref, dvn_ref,
         *scratch) = refs[6 + has_dlse:]
    else:
        dq_ref, dk_ref, dv_ref, *scratch = refs[6 + has_dlse:]
    dq_s, *dkv_s, delta_s = scratch
    i, j = _block_ids(nq, nk, by_keys=True)
    if part2:
        a2, i = _unfold(pl.program_id(2), part2[1], nq)
    # the q block of this step, and whether no key block came to it yet
    at, first = i, j == 0
    if band:
        at = jnp.minimum(i + j, blocks_q - 1)
        first = (first | (i == nq - 1)) & (i + j < blocks_q)
    slot = at       # where this head keeps its dq and delta
    if part2:
        slot = a2 * nq + at
        # the pair's first and last head under its resident blocks
        opens, closes = a2 == 0, a2 == part2[1] - 1

        @_when(opens & (i == 0))
        def _init2():
            dk2_s[:] = jnp.zeros_like(dk2_s)

        @_when(opens & first)
        def _first2():
            dq2_s[at] = jnp.zeros(dq2_s.shape[1:], dq2_s.dtype)

    if dkv_s:
        dk_s, dv_s = dkv_s

        @_when(i == 0)
        def _init():
            dk_s[:] = jnp.zeros_like(dk_s)
            dv_s[:] = jnp.zeros_like(dv_s)

    @_when(first)
    def _first():
        dq_s[slot] = jnp.zeros(dq_s.shape[1:], dq_s.dtype)

    def head(a):
        @_when(first)
        def _row():
            delta, _ = _delta(dy_ref, o_ref, dlse_ref, a, d, g)
            delta_s[slot, a] = delta.T               # [tq, 1] -> [1, tq]

        def panel(cols, segments):
            own = _own(segments[0][2])      # the noised keys' own panel
            k = (kn_ref if own else k_ref)[0, cols, :]
            mine = _lanes(k.shape, a, d, g)
            # head a's lanes alone, so that its dq comes out in them and
            # the sum over the heads of a block is the block's dq
            k = _only(k, mine)
            kk = k * scale
            v = _only((vn_ref if own else v_ref)[0, cols, :], mine)
            if part2:
                k2 = k2_ref[0, cols, :]
                mine2 = _lanes(k2.shape, a2, *part2[:2])
                k2 = _only(k2, mine2)
                kk2 = k2 * scale
            dk = dv = dk2 = 0.0
            for rows, off, how in segments:
                q = q_ref[0, rows, :]
                dy = dy_ref[0, rows, :]
                st = _dot(kk, q, _NT)                # [tk, tq]
                if part2:
                    q2 = q2_ref[0, rows, :]
                    st = st + _dot(kk2, q2, _NT)
                if off is not None:
                    st = _causal(st, off, 1, how)
                pt = jnp.exp(st - lse_ref[a, :, rows])   # row [1, tq]
                dv = dv + _dot(pt.astype(dy.dtype), dy, _NN)
                dst = pt * (_dot(v, dy, _NT) - delta_s[slot, a, :, rows])
                ds = dst.astype(q.dtype)
                dk = dk + _dot(ds, q, _NN)
                dq = dq_s[slot, rows]
                turned = dst.T.astype(k.dtype)
                dq_s[slot, rows] = dq + _dot(turned, k, _NN)
                if part2:
                    dk2 = dk2 + _dot(ds, q2, _NN)
                    dq2_s[at, rows] = dq2_s[at, rows] + _dot(turned, k2, _NN)
            if part2:
                dk2_s[cols] = dk2_s[cols] + _only(dk2, mine2)
            if own or not dkv_s:
                to_k, to_v = (dkn_ref, dvn_ref) if own else (dk_ref, dv_ref)
                whole = (0, cols, slice(None))
                _put(to_k, whole, (dk * scale).astype(to_k.dtype), mine)
                return _put(to_v, whole, dv.astype(to_v.dtype), mine)
            dk_s[cols] = dk_s[cols] + _only(dk, mine)
            dv_s[cols] = dv_s[cols] + _only(dv, mine)

        _walk(panel, i, j, mask, block_q, block_k, tile, by_keys=True,
              half=nq // 2, band=band)

    _each_head(g, head)

    if dkv_s:
        @_when(i == nq - 1)
        def _final():
            dk_ref[0] = (dk_s[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_s[:].astype(dv_ref.dtype)

    if part2:
        @_when(closes & (i == nq - 1))
        def _final2():
            dk2_ref[0] = dk2_s[:] * scale

    last = (i == nq - 1) & (j == nk - 1)
    if part2:
        last = closes & last

    @_when(last)
    def _store_dq():
        # the q blocks down the rows; a pair's heads side by side
        w = g * d
        for n in range(blocks_q):
            rows = slice(n * block_q, (n + 1) * block_q)
            for n2 in range(dq_s.shape[0] // blocks_q):
                dq_ref[0, rows, n2 * w:(n2 + 1) * w] = (
                    dq_s[n2 * blocks_q + n] * scale).astype(dq_ref.dtype)
            if part2:
                dq2_ref[0, rows, :] = (dq2_s[n] * scale).astype(
                    dq2_ref.dtype)


def _bwd_dq_kernel(*refs, mask, scale, block_q, block_k, tile, nq, nk, d,
                   g, has_dlse, part2=None, band=None):
    q_ref, k_ref, v_ref, dy_ref, o_ref, lse_ref = refs[:6]
    dlse_ref = refs[6] if has_dlse else None
    i, j = _block_ids(nq, nk)
    if part2:
        # grid (B * H / g2, nQ, g2 * nK): the g2 heads that share a
        # block of q2 follow one another, so that dq2's block stays in
        # VMEM while each of them stores its own lanes of it
        (q2_ref, k2_ref, dq_ref, delta_ref, dq2_ref, acc_s, lse_s, delta_s,
         acc2_s) = refs[6 + has_dlse:]
        a2, j = _unfold(pl.program_id(2), part2[1], nk)
    elif _own(mask):
        (kn_ref, vn_ref, dq_ref, delta_ref, acc_s, lse_s,
         delta_s) = refs[6 + has_dlse:]
    else:
        dq_ref, delta_ref, acc_s, lse_s, delta_s = refs[6 + has_dlse:]

    @_when(j == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        if part2:
            acc2_s[:] = jnp.zeros_like(acc2_s)

    def head(a):
        @_when(j == 0)
        def _columns():
            # once a q block: lse turned into a column, delta made as
            # one; delta's row goes out to flash_bwd_dkv
            delta, _ = _delta(dy_ref, o_ref, dlse_ref, a, d, g)
            delta_ref[a] = delta.T                   # [tq, 1] -> [1, tq]
            lse_s[a], delta_s[a] = lse_ref[a].T, delta

        def panel(rows, segments):
            q = q_ref[0, rows, :] * scale
            mine = _lanes(q.shape, a, d, g)
            q, dy = _only(q, mine), _only(dy_ref[0, rows, :], mine)
            if part2:
                q2 = q2_ref[0, rows, :] * scale
                q2 = _only(q2, _lanes(q2.shape, a2, *part2[:2]))
            lse, delta = lse_s[a, rows], delta_s[a, rows]
            acc = acc2 = 0.0
            for cols, off, how in segments:
                kk = (kn_ref if _own(how) else k_ref)[0, cols, :]
                s = _dot(q, kk, _NT)                 # [tq, tk]
                if part2:
                    kk2 = k2_ref[0, cols, :]
                    s = s + _dot(q2, kk2, _NT)
                if off is not None:
                    s = _causal(s, off, 0, how)
                p = jnp.exp(s - lse)
                dp = _dot(dy, (vn_ref if _own(how) else v_ref)[0, cols, :],
                          _NT)
                ds = (p * (dp - delta)).astype(kk.dtype)
                acc = acc + _dot(ds, kk, _NN)        # [tq, W]
                if part2:
                    acc2 = acc2 + _dot(ds, kk2, _NN)
            acc_s[rows] = acc_s[rows] + _only(acc, mine)
            if part2:
                acc2_s[rows] = acc2_s[rows] + acc2

        _walk(panel, i, j, mask, block_q, block_k, tile, half=nq // 2,
              band=band)

    _each_head(g, head)

    @_when(j == nk - 1)
    def _final():
        dq_ref[0] = (acc_s[:] * scale).astype(dq_ref.dtype)
        if part2:
            # k2 repeats the shared key under each of the g2 heads'
            # lanes, so every lane group of acc2 holds this head's dq2
            acc2 = acc2_s[:]
            _put(dq2_ref, (0, slice(None), slice(None)),
                 (acc2 * scale).astype(dq2_ref.dtype),
                 _lanes(acc2.shape, a2, *part2[:2]))


def _bwd_dkv_kernel(*refs, mask, scale, block_q, block_k, tile, nq, nk, d,
                    g, part2=None, band=None):
    q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_ref = refs[:6]
    i, jj = _block_ids(nq, nk, by_keys=True)    # q blocks innermost here
    if part2:
        # grid (B, nK, H * nQ): every head's q blocks pass under one
        # resident block of k2, whose gradient is the sum over the heads
        (q2_ref, k2_ref, dk_ref, dv_ref, dk2_ref, dk_s, dv_s,
         dk2_s) = refs[6:]
        at = pl.program_id(2)
        head, i = _unfold(at, part2[2], nq)
        a2 = head % part2[1]

        @pl.when(at == 0)
        def _init2():
            dk2_s[:] = jnp.zeros_like(dk2_s)
    elif _own(mask):
        # the noised keys and values of the key block's rows, and their
        # gradients: whole at the one step whose q block is those rows
        kn_ref, vn_ref, dk_ref, dv_ref, dkn_ref, dvn_ref, dk_s, dv_s = refs[6:]
    else:
        dk_ref, dv_ref, dk_s, dv_s = refs[6:]

    @_when(i == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def head(a):
        def panel(cols, segments):
            # the scores TRANSPOSED, [tk, tq]: k q^T contracts the last
            # dim of both, and p^T, ds^T come out as dv = p^T dy,
            # dk = ds^T q want them, with no contraction over a tile's
            # first dim; the row statistics broadcast down the sublanes
            # as they arrive
            own = _own(segments[0][2])      # the noised keys' own panel
            kk = (kn_ref if own else k_ref)[0, cols, :] * scale  # [tk, W]
            mine = _lanes(kk.shape, a, d, g)
            kk = _only(kk, mine)
            v = _only((vn_ref if own else v_ref)[0, cols, :], mine)
            if part2:
                kk2 = k2_ref[0, cols, :] * scale
            dk = dv = dk2 = 0.0
            for rows, off, how in segments:
                q = q_ref[0, rows, :]
                dy = dy_ref[0, rows, :]
                st = _dot(kk, q, _NT)                # [tk, tq]
                if part2:
                    # this head's lanes of q2 alone: dk2 then comes out
                    # in those lanes, the other heads' in theirs
                    q2 = q2_ref[0, rows, :]
                    q2 = _only(q2, _lanes(q2.shape, a2, *part2[:2]))
                    st = st + _dot(kk2, q2, _NT)
                if off is not None:
                    st = _causal(st, off, 1, how)
                pt = jnp.exp(st - lse_ref[a, :, rows])   # row [1, tq]
                dv = dv + _dot(pt.astype(dy.dtype), dy, _NN)
                dpt = _dot(v, dy, _NT)
                dst = (pt * (dpt - delta_ref[a, :, rows])).astype(q.dtype)
                dk = dk + _dot(dst, q, _NN)
                if part2:
                    dk2 = dk2 + _dot(dst, q2, _NN)
            if own:
                whole = (0, cols, slice(None))
                _put(dkn_ref, whole, (dk * scale).astype(dkn_ref.dtype),
                     mine)
                return _put(dvn_ref, whole, dv.astype(dvn_ref.dtype), mine)
            dk_s[cols] = dk_s[cols] + _only(dk, mine)
            dv_s[cols] = dv_s[cols] + _only(dv, mine)
            if part2:
                dk2_s[cols] = dk2_s[cols] + dk2

        _walk(panel, i, jj, mask, block_q, block_k, tile, by_keys=True,
              half=nq // 2, band=band)

    _each_head(g, head)

    @_when(i == nq - 1)
    def _final():
        dk_ref[0] = (dk_s[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)

    if part2:
        @pl.when(at == part2[2] * nq - 1)
        def _final2():
            dk2_ref[0] = (dk2_s[:] * scale).astype(dk2_ref.dtype)


def _backward_blocks(t, w, block_q, block_k):
    """The backward's major blocks. VMEM guard: the bwd kernels hold six
    [block, w] operands, double buffered, plus float32 accumulators of
    the same shape; with w > 128 at 1024-row blocks that passes the 16 MB
    scoped-vmem limit. Clamp the BACKWARD blocks only. The clamp must
    keep dividing T (a non-divisor block would silently drop query rows
    from dq/dk/dv): shrink to the largest divisor of the incoming block,
    which also divides T."""
    bq, bk = min(block_q, t), min(block_k, t)
    if w > 128:
        bq, bk = _largest_divisor(bq, 512), _largest_divisor(bk, 512)
    return bq, bk


def _backward_of(t, w, block_q, block_k, mask=None, itemsize=2, second=0):
    """Which backward a call of `t` rows (a HALF of them under the
    own-block form) runs, from its shapes alone: what _bwd_pallas is
    told and the lowering counter says. The one kernel where what it
    keeps in VMEM for all rows of a block of heads, dq in float32 and
    its output block twice, is within _RESIDENT_DQ_BYTES: "fused" where
    the backward's blocks hold all of T (always within: a block is no
    larger than _ONE_BLOCK_BYTES; the own-block form's rows are two
    halves, so never one block), "fused_streamed" where T is streamed;
    else "two_kernels". A score of two parts (`second`: the lanes of
    q2's block; `w` then the lanes of the heads that share it) keeps
    dq2 resident beside dq and has no kernel for one block apart: the
    streamed one runs it."""
    if not (_own(mask) or second) and _backward_blocks(
            t, w, block_q, block_k) == (t, t):
        return "fused"
    rows = 2 * t if _own(mask) else t
    if rows * (max(w, _LANES) + second) * (
            4 + 2 * itemsize) <= _RESIDENT_DQ_BYTES:
        return "fused_streamed"
    return "two_kernels"


def _one_kernel_vmem_bytes(t, w, bq, bk, g, itemsize, kv_itemsize, n_kv,
                           n_stats, heads=1, w2=0):
    """The scoped VMEM the streamed flash_bwd asks for, from its shapes:
    dq for all rows in float32 and its output block, each operand and
    output block twice (the pipeline's two buffers), the float32 scratch
    of a key block's dk and dv, the row statistics (a [1, rows] float32
    row takes eight sublanes), and four float32 copies of the largest
    panel's scores for what is live inside it. The compiler took the
    kernel with 6 MB for that last part at 1024 x 1024 panels (the least
    limit it accepted, compiled for a described v5e: 28 MiB at T 16,384,
    23 under the own-block form at 2 x 4,096, 15 at T 4,096; PR 39), and
    a larger limit costs the kernel nothing: the same device time under
    36 MiB and under 70. A score of two parts: `heads` heads of `w`
    lanes share a q2 block of `w2` lanes; dq of each and dq2 stay
    resident, q2 and k2 are two operand blocks more and dk2 one float32
    output block with its scratch."""
    w = max(w, _LANES)
    resident = t * (heads * w + w2) * (4 + 2 * itemsize)
    blocks = 2 * itemsize * (w * (3 * bq + n_kv * bk) + w2 * (bq + bk))
    grads = n_kv * bk * w * 2 * kv_itemsize + 2 * bk * w * 4 + 3 * bk * w2 * 4
    stats = heads * g * 8 * 4 * (2 * n_stats * bq + t)
    panel = 4 * 4 * min(bq * bk, max(_PANEL_SCORES, _LANES * bk))
    return resident + blocks + grads + stats + panel


def _group_sum(dkv, group, d, dtype):
    """dk or dv as the kernels leave it under grouped key/value heads,
    [B, T, H*D] in float32 with one head's worth a QUERY head, summed
    over each group of `group` query heads: [B, T, (H / group) * D].
    As sums of the heads' lane slices, which XLA fuses into one pass
    over dkv; as a reduce over a [B, T, H / group, group, D] view it
    first copied the whole array into another tiling (0.41 ms of a
    134 MB array beside the 0.18 ms reduce, my chip run, PR 37)."""
    heads = [dkv[..., a * d:(a + 1) * d] for a in range(dkv.shape[-1] // d)]
    return jnp.concatenate(
        [sum(heads[a + 1:a + group], heads[a])
         for a in range(0, len(heads), group)], -1).astype(dtype)


def _under_query_heads(kv, d, group):
    """k or v [B, T, Hkv*D] with each head `group` times side by side,
    [B, T, Hkv*group*D]: head h of the result is head h // group of kv.
    As lane slices put side by side, which XLA writes in one pass
    (_group_sum has the reason: a [.., Hkv, group, D] view is a copy
    into another tiling first)."""
    return jnp.concatenate(
        [kv[..., a * d:(a + 1) * d] for a in range(kv.shape[-1] // d)
         for _ in range(group)], -1)


def _backward_for(q, n_head, mask, block_q, block_k):
    """_backward_of for the operand q [B, T, H*D] of a one-part call."""
    t, hd = q.shape[1:]
    d = hd // n_head
    return _backward_of(t // 2 if _own(mask) else t,
                        heads_per_block(n_head, d) * d, block_q, block_k,
                        mask, q.dtype.itemsize)


def _backward2_for(q, q2, n_head, block_q, block_k):
    """_backward_of for q [B, T, H*D] and q2 [B, T, H*D2] of a two-part
    call: the heads that share a block of q2 stay resident together."""
    t, hd = q.shape[1:]
    g2 = _part2_of(n_head, q2)[1]
    return _backward_of(t, g2 * hd // n_head, block_q, block_k,
                        itemsize=q.dtype.itemsize,
                        second=g2 * q2.shape[-1] // n_head)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _bwd_pallas(res, dy, n_head, n_kv_head, mask, scale, block_q, block_k,
                interpret, backward, dlse=None):
    """`backward`: _backward_of's word for these shapes (_backward_for)."""
    q, k, v, o, lse = res
    b, t, hd = q.shape
    d = hd // n_head
    g = heads_per_block(n_head, d)
    group = n_head // n_kv_head
    w = g * d
    own = _own(mask)
    # the own-block form: q blocks through both halves [noised; clean],
    # `nk` key blocks of the clean half, equal blocks (_fwd_pallas)
    rows_k = t // 2 if own else t
    bq, bk = _backward_blocks(rows_k, w, block_q, block_k)
    nq, nk = t // bq, rows_k // bk
    tile = _tile(bq, _TILE)
    stats = [lse.reshape(b * n_head, 1, t)]
    if dlse is not None:
        stats.append(dlse.astype(jnp.float32).reshape(b * n_head, 1, t))
    rows, kv, stat = _specs(n_head, g, d, group)
    bthd = jax.ShapeDtypeStruct((b, t, hd), q.dtype)
    # grouped key/value heads: a grid step still makes ONE query head's
    # dk and dv, in float32, and XLA sums each group's after the kernel
    # (_group_sum)
    dkv = jax.ShapeDtypeStruct((b, rows_k, hd),
                               q.dtype if group == 1 else jnp.float32)
    summed = (lambda x: x) if group == 1 else functools.partial(
        _group_sum, group=group, d=d, dtype=k.dtype)
    if own:
        # the keys a step walks are the clean half's, `nk` blocks on,
        # and the noised keys and values of a q block's own rows (of
        # the key block's, by keys) come as two operands more
        clean = lambda axis: lambda s: s[axis] + nk
        noised_q = [kv(bq, lambda s: jnp.minimum(s[1], nk - 1))] * 2
        noised_k = [kv(bk, 1)] * 2
        kn = (k, v)
    else:
        clean = lambda axis: axis
        noised_q, noised_k, kn = [], [], ()
    # what the two kernels' inner grid axis counts and where it points:
    # every key block of a q block (every q block of a key block, by
    # keys), or under a window the band's steps alone (_fwd_pallas)
    steps_k, steps_q, keys_at, rows_at, band = nk, nq, clean(2), 2, None
    if _band(mask):
        steps_k = steps_q = _band_steps(mask[2], bk, nk)
        band = (steps_k, nq)
        keys_at = lambda s: jnp.maximum(s[1] - (steps_k - 1) + s[2], 0)
        rows_at = lambda s: jnp.minimum(s[1] + s[2], nq - 1)

    def halves(dk, dv, dkn):
        if own:
            # [noised; clean], as k and v came
            return tuple(jnp.concatenate([summed(x), summed(y)], 1)
                         for x, y in zip(dkn, (dk, dv)))
        return summed(dk), summed(dv)

    # PR 31's kernel, as it was. It lives in the compiler's default VMEM
    # and must ask for no more: a limit over the 16 MiB default, asked of
    # opt350m_train's one-block calls, cost the cell 0.7% (XLA stages
    # fewer of the matmuls' operands in VMEM round such a call; my chip
    # runs, PR 39).
    if backward == "fused":
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, mask=mask, scale=scale,
                              t=t, tile=tile, d=d, g=g,
                              has_dlse=dlse is not None),
            grid=(b * n_head // g,),
            in_specs=[rows(t), kv(t), kv(t), rows(t), rows(t)]
            + [stat(t)] * len(stats),
            out_specs=[rows(t)] * 3,
            out_shape=[bthd, dkv, dkv],
            scratch_shapes=[pltpu.VMEM((t, w), jnp.float32),
                            pltpu.VMEM((1, t), jnp.float32)],
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, dy, o, *stats)
        return dq, summed(dk), summed(dv)

    if backward == "fused_streamed":
        # grid (B * H / g, nK, steps_q): flash_bwd_dkv's, the q-side
        # operands following the LAST axis, and dq's block all of T; one
        # q block a key block needs no scratch for dk and dv
        dq, dk, dv, *dkn = pl.pallas_call(
            functools.partial(_bwd_one_kernel, mask=mask, scale=scale,
                              block_q=bq, block_k=bk, nq=steps_q, nk=nk,
                              blocks_q=nq, tile=tile, d=d, g=g,
                              has_dlse=dlse is not None, band=band),
            grid=(b * n_head // g, nk, steps_q),
            in_specs=[rows(bq, rows_at), kv(bk, clean(1)), kv(bk, clean(1)),
                      rows(bq, rows_at), rows(bq, rows_at)]
            + [stat(bq, rows_at)] * len(stats) + noised_k,
            out_specs=[rows(t)] + [rows(bk, 1)] * (2 + len(kn)),
            out_shape=[bthd] + [dkv] * (2 + len(kn)),
            scratch_shapes=[pltpu.VMEM((nq, bq, w), jnp.float32)]
            + [pltpu.VMEM((bk, w), jnp.float32)] * (2 * (steps_q > 1))
            + [pltpu.VMEM((nq, g, 1, bq), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_one_kernel_vmem_bytes(
                    t, w, bq, bk, g, q.dtype.itemsize, dkv.dtype.itemsize,
                    2 + len(kn), len(stats))),
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, dy, o, *stats, *kn)
        return (dq,) + halves(dk, dv, dkn)

    dq, delta3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, mask=mask, scale=scale,
                          block_q=bq, block_k=bk, nq=nq, nk=steps_k,
                          tile=tile, d=d, g=g, has_dlse=dlse is not None,
                          band=band),
        grid=(b * n_head // g, nq, steps_k),
        in_specs=[rows(bq, 1), kv(bk, keys_at), kv(bk, keys_at),
                  rows(bq, 1), rows(bq, 1)] + [stat(bq, 1)] * len(stats)
        + noised_q,
        out_specs=[rows(bq, 1), stat(bq, 1)],
        out_shape=[bthd, jax.ShapeDtypeStruct((b * n_head, 1, t),
                                              jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, dy, o, *stats, *kn)

    # grid (B * H / g, nK, nQ): the q-side operands follow the LAST axis
    dk, dv, *dkn = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, mask=mask, scale=scale,
                          block_q=bq, block_k=bk, nq=steps_q, nk=nk,
                          tile=tile, d=d, g=g, band=band),
        grid=(b * n_head // g, nk, steps_q),
        in_specs=[rows(bq, rows_at), kv(bk, clean(1)), kv(bk, clean(1)),
                  rows(bq, rows_at), stat(bq, rows_at), stat(bq, rows_at)]
        + noised_k,
        out_specs=[rows(bk, 1)] * (2 + len(kn)),
        out_shape=[dkv] * (2 + len(kn)),
        scratch_shapes=[pltpu.VMEM((bk, w), jnp.float32),
                        pltpu.VMEM((bk, w), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, dy, stats[0], delta3, *kn)
    return (dq,) + halves(dk, dv, dkn)


# --------------------------------------------------------------------------
# A score of two parts (ISSUE 34: latent attention). s = q k^T + q2 k2^T
# where q2 is [B, T, H*D2], a second part a head, and k2 [B, T, D2] is
# ONE key that every query head reads; values keep k's width, so a
# head's key is D + D2 wide and its value D. Nothing is padded or
# repeated over the heads in HBM: q2's block is 128 lanes, g2 = 128 / D2
# heads of it (one where D2 is a multiple of 128), of which a grid step
# keeps its own head's lanes (_only) and contracts all 128 against k2
# with the shared key REPEATED under each of the g2 lane groups (a
# [B, T, 128] operand that _attend makes outside the custom_vjp, whose
# transpose folds dk2's lane groups back). The kernels are the streamed
# ones with `part2` = (D2, g2, H) set, under the same names; a block is
# one head (D a multiple of 128). Their grids differ from the one-part
# grids where an output is shared between heads. The ONE backward
# kernel flash_bwd (ISSUE 56; _bwd_one_kernel has the account) runs the
# g2 heads of a q2 block in turn under each key block, grid
# (B * H / g2, nK, g2 * nQ): dq of both heads and dq2 stay in VMEM for
# all of T (T x 128 x 24 bytes at g2 2 in bf16: 25 MB at T 8,192), and
# dk2 leaves the kernel as float32 partials [B * H / g2, T, 128], a
# pair of heads each, that XLA adds up (16 x 8192 x 128 x 4 B = 67 MB
# written and read a call at T 8,192). Beyond the byte bound the two
# kernels: flash_bwd_dq runs the g2 heads of a q2 block one after the
# other, so that dq2's block stays in VMEM while each stores its lanes;
# flash_bwd_dkv runs ALL heads under one resident block of k2 and sums
# dk2 over them in float32 scratch.
def _part2_of(n_head, q2):
    d2 = q2.shape[-1] // n_head
    g2 = max(_LANES // d2, 1)
    return d2, g2, n_head


def _specs2(n_head, g2, w2, fold):
    """Block specs of the two-part kernels for one head to a block of
    the first part. `fold(s)` -> (batch, head, q block, key block) of a
    grid step; returns builders of (q-side rows of W lanes, key-side
    rows, a row statistic, q2's rows, k2's rows)."""
    def rows(block, w=_LANES):
        return pl.BlockSpec((1, block, w), lambda *s: (
            fold(s)[0], fold(s)[2], fold(s)[1]))

    def keys(block, w=_LANES):
        return pl.BlockSpec((1, block, w), lambda *s: (
            fold(s)[0], fold(s)[3], fold(s)[1]))

    def stat(block):
        return pl.BlockSpec((1, 1, block), lambda *s: (
            fold(s)[0] * n_head + fold(s)[1], 0, fold(s)[2]))

    def rows2(block):
        return pl.BlockSpec((1, block, w2), lambda *s: (
            fold(s)[0], fold(s)[2], fold(s)[1] // g2))

    def keys2(block):
        return pl.BlockSpec((1, block, w2), lambda *s: (
            fold(s)[0], fold(s)[3], 0))

    return rows, keys, stat, rows2, keys2


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def _fwd_pallas2(q, k, v, q2, k2, n_head, mask, scale, block_q, block_k,
                 interpret):
    b, t, hd = q.shape
    d = hd // n_head
    part2 = _part2_of(n_head, q2)
    w2 = k2.shape[-1]
    bq, bk = min(block_q, t), min(block_k, t)
    nq, nk = t // bq, t // bk
    rows, keys, stat, rows2, keys2 = _specs2(
        n_head, part2[1], w2,
        lambda s: (s[0] // n_head, s[0] % n_head, s[1], s[2]))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, mask=mask, scale=scale,
                          block_q=bq, block_k=bk, tile=_tile(bq, _TILE),
                          nq=nq, nk=nk, d=d, g=1, part2=part2),
        grid=(b * n_head, nq, nk),
        in_specs=[rows(bq, d), keys(bk, d), keys(bk, d), rows2(bq),
                  keys2(bk)],
        out_specs=[rows(bq, d), stat(bq)],
        out_shape=[jax.ShapeDtypeStruct((b, t, hd), q.dtype),
                   jax.ShapeDtypeStruct((b * n_head, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bq, 1), jnp.float32),
                        pltpu.VMEM((1, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)] if nk > 1 else [],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, q2, k2)
    return out, lse.reshape(b, n_head, t)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def _bwd_pallas2(res, dy, n_head, mask, scale, block_q, block_k, interpret,
                 backward):
    """`backward`: _backward_of's word for these shapes (_backward2_for)."""
    q, k, v, q2, k2, o, lse = res
    b, t, hd = q.shape
    d = hd // n_head
    part2 = _part2_of(n_head, q2)
    g2, w2 = part2[1], k2.shape[-1]
    bq, bk = _backward_blocks(t, d, block_q, block_k)
    nq, nk = t // bq, t // bk
    tile = _tile(bq, _TILE)
    lse3 = lse.reshape(b * n_head, 1, t)
    stat_shape = jax.ShapeDtypeStruct((b * n_head, 1, t), jnp.float32)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    pairs = n_head // g2

    if backward == "fused_streamed":
        def fold(s):
            a2, i = _unfold(s[2], g2, nq)
            return s[0] // pairs, s[0] % pairs * g2 + a2, i, s[1]

        rows, keys, stat, rows2, keys2 = _specs2(n_head, g2, w2, fold)
        # what stays in VMEM from a pair's first step to its last: dq,
        # the pair's heads side by side, and dq2, both for all of T
        whole = lambda w: pl.BlockSpec(
            (1, t, w), lambda *s: (s[0] // pairs, 0, s[0] % pairs))
        dq, dk, dv, dq2, dk2 = pl.pallas_call(
            functools.partial(_bwd_one_kernel, mask=mask, scale=scale,
                              block_q=bq, block_k=bk, nq=nq, nk=nk,
                              blocks_q=nq, tile=tile, d=d, g=1,
                              has_dlse=False, part2=part2),
            grid=(b * pairs, nk, g2 * nq),
            in_specs=[rows(bq, d), keys(bk, d), keys(bk, d), rows(bq, d),
                      rows(bq, d), stat(bq), rows2(bq), keys2(bk)],
            out_specs=[whole(g2 * d), keys(bk, d), keys(bk, d), whole(w2),
                       pl.BlockSpec((1, bk, w2), lambda *s: (s[0], s[1], 0))],
            out_shape=[like(q), like(k), like(v), like(q2),
                       jax.ShapeDtypeStruct((b * pairs, t, w2), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((g2 * nq, bq, d), jnp.float32)]
            + [pltpu.VMEM((bk, d), jnp.float32)] * (2 * (nq > 1))
            + [pltpu.VMEM((g2 * nq, 1, 1, bq), jnp.float32),
               pltpu.VMEM((nq, bq, w2), jnp.float32),
               pltpu.VMEM((bk, w2), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_one_kernel_vmem_bytes(
                    t, d, bq, bk, 1, q.dtype.itemsize, k.dtype.itemsize, 2,
                    1, g2, w2)),
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, dy, o, lse3, q2, k2)
        # the pairs' partials of the one shared key, added as slices
        # (_group_sum's way: a reduce over a reshaped view is a copy
        # into another tiling first)
        dk2 = dk2.reshape(b, pairs, t, w2)
        dk2 = functools.reduce(
            jnp.add, [dk2[:, n] for n in range(pairs)]).astype(k2.dtype)
        return dq, dk, dv, dq2, dk2

    def fold_dq(s):
        a2, j = _unfold(s[2], g2, nk)
        return s[0] // pairs, s[0] % pairs * g2 + a2, s[1], j

    rows, keys, stat, rows2, keys2 = _specs2(n_head, g2, w2, fold_dq)
    dq, delta3, dq2 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, mask=mask, scale=scale,
                          block_q=bq, block_k=bk, nq=nq, nk=nk, tile=tile,
                          d=d, g=1, has_dlse=False, part2=part2),
        grid=(b * pairs, nq, g2 * nk),
        in_specs=[rows(bq, d), keys(bk, d), keys(bk, d), rows(bq, d),
                  rows(bq, d), stat(bq), rows2(bq), keys2(bk)],
        out_specs=[rows(bq, d), stat(bq), rows2(bq)],
        out_shape=[like(q), stat_shape, like(q2)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((1, bq, 1), jnp.float32),
                        pltpu.VMEM((1, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, w2), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, dy, o, lse3, q2, k2)

    def fold_dkv(s):
        head, i = _unfold(s[2], n_head, nq)
        return s[0], head, i, s[1]

    rows, keys, stat, rows2, keys2 = _specs2(n_head, g2, w2, fold_dkv)
    dk, dv, dk2 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, mask=mask, scale=scale,
                          block_q=bq, block_k=bk, nq=nq, nk=nk, tile=tile,
                          d=d, g=1, part2=part2),
        grid=(b, nk, n_head * nq),
        in_specs=[rows(bq, d), keys(bk, d), keys(bk, d), rows(bq, d),
                  stat(bq), stat(bq), rows2(bq), keys2(bk)],
        out_specs=[keys(bk, d), keys(bk, d), keys2(bk)],
        out_shape=[like(k), like(v), like(k2)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, w2), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, dy, lse3, delta3, q2, k2)
    return dq, dk, dv, dq2, dk2


# The names a recompute region keeps by (ops/control_flow.py's
# recompute_block saves these and nothing else): the forward kernel's two
# results, which are all of it that a backward kernel reads. Each fwd
# rule below names them BEFORE they go into the primal result and the
# residuals, so both ARE the named value. A name put on _flash's result
# outside the custom_vjp covers a copy, and the kernel's own output,
# which the backward holds, is recomputed all the same; a name on the
# residuals alone leaves the primal result, which the output
# projection's weight gradient reads, to be recomputed by the kernel
# (tried: the forward runs twice again). Outside a region, and under a
# jax.checkpoint with no policy, a name is an identity that XLA never
# sees.
KEPT_IN_REGIONS = ("flash_out", "flash_lse")


def _named(out, lse):
    return (checkpoint_name(out, KEPT_IN_REGIONS[0]),
            checkpoint_name(lse, KEPT_IN_REGIONS[1]))


# static: n_head, mask, scale, block_q, block_k, interpret
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash2(q, k, v, q2, k2, *static):
    return _fwd_pallas2(q, k, v, q2, k2, *static)[0]


def _flash2_fwd(q, k, v, q2, k2, *static):
    out, lse = _named(*_fwd_pallas2(q, k, v, q2, k2, *static))
    return out, (q, k, v, q2, k2, out, lse)


def _flash2_bwd(*args):
    *static, res, dy = args
    n_head, _, _, block_q, block_k, _ = static
    return _bwd_pallas2(res, dy, *static, _backward2_for(
        res[0], res[3], n_head, block_q, block_k))


_flash2.defvjp(_flash2_fwd, _flash2_bwd)


# --------------------------------------------------------------------------
# The static arguments of every entry below, in order: n_head,
# n_kv_head, mask (None, or _causal's (shift, strict)), scale, block_q,
# block_k, interpret.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, *static):
    return _fwd_pallas(q, k, v, *static)[0]


def _flash_fwd(q, k, v, *static):
    out, lse = _named(*_fwd_pallas(q, k, v, *static))
    return out, (q, k, v, out, lse)


def _backward(static, res, dy, dlse=None):
    """_bwd_pallas, told which backward these shapes take: decided here,
    outside its jit, so that the choice is part of what the jit caches
    by."""
    n_head, _, mask, _, block_q, block_k, _ = static
    return _bwd_pallas(res, dy, *static, _backward_for(
        res[0], n_head, mask, block_q, block_k), dlse=dlse)


def _flash_bwd(*args):
    *static, res, dy = args
    return _backward(static, res, dy)


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# (out, lse) variant: same kernels, but the log-sum-exp rows are a public,
# differentiable output. Ring attention combines per-shard partial results
# with these (parallel/ring.py), so d(loss)/d(lse) is generally non-zero.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, *static):
    return _fwd_pallas(q, k, v, *static)


def _flash_lse_fwd(q, k, v, *static):
    out, lse = _named(*_fwd_pallas(q, k, v, *static))
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(*args):
    *static, res, (dy, dlse) = args
    return _backward(static, res, dy, dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _on_tpu(x):
    try:
        return list(x.devices())[0].platform == "tpu"
    except Exception:
        return jax.default_backend() == "tpu"


def _largest_divisor(n, limit):
    """Largest d <= limit with n % d == 0 (block-size fitting; trace-time
    only, n is a static shape)."""
    d = min(limit, n)
    while d > 1 and n % d:
        d -= 1
    return d


def _auto_block(t, w, itemsize):
    """Major block for a sequence of t rows: all of it where a [t, w]
    operand (w: the lanes of a block, g heads) is small in VMEM (lanes
    pad w to 128) and t can be cut into panels, so that the grid has one
    step a block of heads and nothing is carried between steps;
    otherwise the largest divisor of t up to _AUTO_BLOCK, streamed."""
    if t % _LANES == 0 and t * max(w, _LANES) * itemsize <= _ONE_BLOCK_BYTES:
        return t
    return _largest_divisor(t, _AUTO_BLOCK)


_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_flash_lowerings_total",
    "flash attention dispatches at trace time (one a lowering of the op, "
    "none a step): the path taken, the layout of the entry called, the "
    "heads a kernel block holds, the backward its gradient would run "
    "(fused: one kernel, all of T in a block; fused_streamed: one "
    "kernel too, T streamed and dq for all rows held in VMEM, with or "
    "without a second score part; two_kernels: dq, then dk and dv, for a "
    "T over that kernel's byte bound; none: dense math), "
    "the mask (none, causal, block_causal, block_causal_strict, "
    "block_causal_own: block diffusion's [noised; clean] halves), the "
    "query heads that read one key/value head, a head's key and value "
    "widths, its score's second part (none, or shared: one key that "
    "every head reads) and the window that bounds the keys a query sees "
    "(0: none)",
    ("path", "entry", "heads_per_block", "backward", "mask", "kv_groups",
     "key_width", "value_width", "second_part", "window"))
_BAND_SCORES = _REG.counter(
    "ptpu_flash_band_scores_total",
    "scores of the flash kernels' walks under a window bound, added at "
    "trace time by each lowering that takes the kernels, a batch row "
    "and head each: kind computed (what the walk's blocks and panels "
    "hold, masked tiles whole) and useful (what the band holds), for the "
    "forward walk and the backward's: by keys alone where one kernel runs "
    "it, by queries and by keys where two do",
    ("window", "walk", "kind"))


def _resolve_path(q, scale, block_q, block_k, force):
    """Shared dispatch: (path, scale, bq, bk), from the heads' shape and
    dtype: q is anything with .shape [B, H, T, D] and .dtype (the
    operands themselves stay [B, T, H*D]). path: "pallas" / "interpret" /
    "dense" — auto picks the kernel on TPU when T divides the blocks
    and the heads tile onto the lanes. block None → auto (_auto_block):
    all of T where that is small in VMEM, else the largest divisor of T
    up to 1024 — a divisor, so non-power-of-two T (1536, ...) keeps the
    fused kernel instead of demoting to dense."""
    n_head, t, d = q.shape[1:]
    g = heads_per_block(n_head, d)
    w = g * d
    scale = float(scale) if scale else d ** -0.5
    auto_degenerate = False
    if not block_q or not block_k:
        auto = _auto_block(t, w, q.dtype.itemsize)
        # a T with no divisor >= 128 below the cap (prime, 2*prime, ...)
        # would yield a near-T^2 grid of tiny blocks — far worse than
        # dense XLA; demote instead of silently compiling a cliff
        auto_degenerate = auto < min(128, t)
        block_q = block_q or auto
        block_k = block_k or auto
    block_q, block_k = min(block_q, t), min(block_k, t)
    path = force
    if path is None:
        # several heads that fill no whole lane tile go as ONE block of
        # all H*D lanes: only where a grid step's [rows, H*D] operand is
        # as small as a block may be
        fits = (g == 1 or w == _LANES
                or max(block_q, block_k) * max(w, _LANES)
                * q.dtype.itemsize <= _ONE_BLOCK_BYTES)
        usable = (t % block_q == 0 and t % block_k == 0 and t >= 128
                  and d % 8 == 0 and fits and not auto_degenerate)
        path = "pallas" if (usable and _on_tpu(q)) else "dense"
    return path, scale, block_q, block_k


def heads_first(x, n_head):
    """[B, T, H*D] -> [B, H, T, D]."""
    b, t, hd = x.shape
    return x.reshape(b, t, n_head, hd // n_head).transpose(0, 2, 1, 3)


def heads_last(x):
    """[B, H, T, D] -> [B, T, H*D]."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _mask_of(causal, mask_block, strict, own_block=False, window=None):
    """(the kernels' mask, its label): None / "none" where not causal,
    else _causal's (shift, form) for blocks of `mask_block` rows, a
    power of two: form 0, 1 (`strict`) or _OWN (`own_block`); under a
    `window` (0, _WIN, window), labelled "causal" (the window is a
    label of its own)."""
    if not causal:
        if own_block or window:
            raise ValueError("flash attention: own_block and window "
                             "are causal masks")
        return None, "none"
    if window:
        if window < 1 or mask_block != 1 or strict or own_block:
            raise ValueError(
                "flash attention: a window of %r keys goes with plain "
                "causal alone, not with a mask in blocks, strict or "
                "own_block" % (window,))
        return (0, _WIN, int(window)), "causal"
    shift = int(mask_block).bit_length() - 1
    if mask_block < 1 or 1 << shift != mask_block:
        raise ValueError("flash attention: the mask's block is a power "
                         "of two, got %r" % (mask_block,))
    if own_block:
        if strict:
            raise ValueError("flash attention: own_block has its own "
                             "strict half, `strict` is the other forms'")
        return (shift, _OWN), "block_causal_own"
    return (shift, int(bool(strict))), "%scausal%s" % (
        "block_" if shift else "", "_strict" if strict else "")


def _attend(q, k, v, n_head, causal, scale, block_q, block_k, force,
            entry, with_lse, n_kv_head=None, mask_block=1, strict=False,
            q2=None, k2=None, own_block=False, window=None):
    """Dispatch of every entry: q/k/v [B, T, H*D] -> out, or (out, lse
    [B, H, T]) `with_lse`. `entry` labels the count: the layout the
    caller came in. k and v may hold `n_kv_head` < H heads, [B, T,
    Hkv*D]: query head h reads head h // (H / Hkv), and the kernels
    take that where a block is one head (D a multiple of 128) and,
    with k and v spread under the query heads' lanes, where the heads
    of a block divide a group (two heads of 64 in groups of 4). q2
    [B, T, H*D2] and k2 [B, T, D2] add q2_h k2^T to head h's scores
    (the kernels: one head to a block, no groups, D2 a multiple of 128
    or dividing it in as many heads as divide H); `scale` then defaults
    to (D + D2)^-0.5. `own_block`: flash_bthd's; the kernels' blocks are
    then cut from a HALF of the rows. `window`: flash_bthd's; one that
    holds every key of the sequence is no window."""
    b, t, hd = q.shape
    d = hd // n_head
    n_kv_head = n_kv_head or n_head
    d2 = 0
    if q2 is not None:
        d2 = k2.shape[-1]
        if q2.shape != (b, t, n_head * d2) or k2.shape != (b, t, d2):
            raise ValueError(
                "flash attention: a second score part wants q2 [B, T, "
                "H*D2] and ONE shared key k2 [B, T, D2], got %s and %s "
                "beside q %s" % (q2.shape, k2.shape, q.shape))
        scale = scale or (d + d2) ** -0.5
    if n_head % n_kv_head or k.shape[-1] != n_kv_head * d:
        raise ValueError(
            "flash attention: %d query heads of %d cannot read k of "
            "shape %s as %d heads" % (n_head, d, k.shape, n_kv_head))
    if window and q2 is not None:
        raise ValueError("flash attention: a window does not go with a "
                         "second score part")
    if window and causal and window >= t:
        window = None
    mask, mask_label = _mask_of(causal, mask_block, strict, own_block,
                                window)
    if own_block and t % 2:
        raise ValueError("flash attention: own_block wants two halves of "
                         "rows, got %d" % t)
    rows = t // 2 if own_block else t       # what the blocks are cut from
    path, scale, bq, bk = _resolve_path(
        jax.ShapeDtypeStruct((b, n_head, rows, d), q.dtype), scale, block_q,
        block_k, force)
    g = heads_per_block(n_head, d)
    group = n_head // n_kv_head
    # what the kernels cannot take goes the dense way whoever asked: the
    # g > 1 heads of a block read ONE key/value head only where g
    # divides the group (`spread`, below), and a mask's block must
    # divide every tile's edge
    spread = group > 1 and g > 1 and group % g == 0
    edge = _tile(_backward_blocks(rows, g * d, bq, bk)[0], _TILE)
    if (group > 1 and g > 1 and not spread) or (
            mask and any(x % (1 << mask[0]) for x in (bq, bk, edge))):
        path = "dense"
    # the own-block form's halves are walked in equal blocks, and it has
    # neither an lse to give nor a second part
    if own_block and (bq != bk or rows % bq or with_lse or q2 is not None):
        path = "dense"
    if window and bq != bk:         # the band is walked in equal blocks
        path = "dense"
    if d2:
        g2 = _part2_of(n_head, q2)[1]
        if (g > 1 or n_kv_head != n_head or with_lse or n_head % g2
                or (d2 % _LANES and _LANES % d2)):
            path = "dense"
    if v.shape[-1] != k.shape[-1]:      # the kernels' value is D wide
        path = "dense"
    backward = ("none" if path == "dense"
                else _backward2_for(q, q2, n_head, bq, bk) if d2
                else _backward_for(q, n_head, mask, bq, bk))
    _LOWERINGS.inc(path=path, entry=entry, heads_per_block=str(g),
                   backward=backward, mask=mask_label,
                   kv_groups=str(group),
                   key_width=str(d + d2),
                   value_width=str(v.shape[-1] // n_kv_head),
                   second_part="shared" if d2 else "none",
                   window=str(window or 0))
    if window and path != "dense":
        back = _backward_blocks(t, g * d, bq, bk)[0]
        walks = [("forward", bq, False), ("backward_by_keys", back, True)]
        if backward == "two_kernels":       # the one kernel has no such walk
            walks.append(("backward_by_queries", back, False))
        for walk, block, by_keys in walks:
            for kind, scores in zip(("computed", "useful"), band_scores(
                    t, block, _tile(block, _TILE), window, by_keys)):
                _BAND_SCORES.inc(scores, window=str(window), walk=walk,
                                 kind=kind)
    if path == "dense":
        out, lse = _dense_lse(
            heads_first(q, n_head), heads_first(k, n_kv_head),
            heads_first(v, n_kv_head), causal, scale, mask or (0, 0),
            None if q2 is None else heads_first(q2, n_head), k2)
        return (heads_last(out), lse) if with_lse else heads_last(out)
    if d2:
        # the shared key under each of the g2 heads' lanes of q2's
        # block: made here, outside the custom_vjp, so that autodiff
        # folds dk2's lane groups back into one
        return _flash2(q, k, v, q2, jnp.tile(k2, (1, 1, g2)), n_head, mask,
                       scale, bq, bk, path == "interpret")
    if spread:
        # several heads to a block, all of one group: each key/value
        # head under each of its query heads' lanes, [B, T, H*D], made
        # here, outside the custom_vjp, so that autodiff folds dk and dv
        # back over the group; the kernels then run as without groups
        k, v = _under_query_heads(k, d, group), _under_query_heads(
            v, d, group)
        n_kv_head = n_head
    return (_flash_lse if with_lse else _flash)(
        q, k, v, n_head, n_kv_head, mask, scale, bq, bk,
        path == "interpret")


def flash_bthd(q, k, v, n_head, causal=False, scale=None,
               block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
               force=None, n_kv_head=None, mask_block=1, strict=False,
               q2=None, k2=None, own_block=False, window=None):
    """Fused multi-head attention in the projections' own layout.
    q and the result: [B, T, H*D], head h in lanes [h D, (h+1) D); k and
    v the same, or [B, T, Hkv*D] with `n_kv_head` = Hkv heads, each read
    by H / Hkv query heads (dk and dv are the sums over them).

    `q2` [B, T, H*D2] and `k2` [B, T, D2] make the score the sum of two
    products, q_h k_h^T + q2_h k2^T, the second against ONE key that
    every head reads (latent attention's rotary part): a head's key is
    D + D2 wide, its value D, and dk2 is the sum over the heads.

    `causal` with `mask_block` m (a power of two) masks in blocks of m
    rows: a query sees the keys of its own block and of the blocks
    before it, and with `strict` only of the blocks before it (a first
    block's rows then see nothing: their output is finite and their lse
    -1e30, which weighs nothing where partial results are merged by
    lse). m 1 and no `strict` is plain causal.

    `own_block` (with `causal`; block diffusion): the T rows are two
    halves at the same positions, [noised; clean]. A clean query sees
    the clean keys of its own block and of the blocks before it and no
    noised key; a noised query the clean keys of the blocks BEFORE its
    own and the noised keys of its OWN block (so every row sees a key).
    One call of each kernel walks both halves: q, k, v, the result and
    the gradients are the [B, 2L, .] arrays as they are, no half is
    sliced out or put back.

    `window` w (with `causal`; a static integer): query i sees the keys
    j with `i - w < j <= i`, its own and the w - 1 before it. Key
    blocks wholly under that band are not walked (the module's
    docstring). It goes with `n_kv_head` and with nothing else: with
    `mask_block` > 1, `strict`, `own_block` or a second part it raises;
    w >= T is plain causal.

    force: None = auto (Pallas kernel on TPU when T divides the blocks,
    dense XLA math otherwise), "pallas" / "interpret" / "dense" pin a path
    (tests use "interpret" to run the kernel on CPU).
    """
    return _attend(q, k, v, n_head, causal, scale, block_q, block_k, force,
                   "bthd", False, n_kv_head, mask_block, strict, q2, k2,
                   own_block, window)


def flash_bthd_lse(q, k, v, n_head, causal=False, scale=None,
                   block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                   force=None, n_kv_head=None, mask_block=1, strict=False,
                   window=None):
    """Like flash_bthd but returns (out [B, T, H*D], lse [B, H, T]) with
    lse[b,h,i] = logsumexp_j(q_i·k_j*scale [+mask]) — the statistic ring
    attention needs to merge partial attention over K/V shards. Both
    outputs are differentiable (the lse cotangent folds into the shared
    backward kernels)."""
    return _attend(q, k, v, n_head, causal, scale, block_q, block_k, force,
                   "bthd", True, n_kv_head, mask_block, strict,
                   window=window)


def diff_heads(q, n_head, turn):
    """q [B, T, H*D] with the lanes of every other head zeroed: those of
    the odd heads (`turn` 0) or of the even ones (`turn` 1). Read as H /
    2 heads of 2D lanes, head p is then (q_2p, 0) or (0, q_2p+1), and
    against a PAIR of key heads side by side, (k_2r, k_2r+1), which is
    the layout a projection leaves them in, its product over the 2D
    lanes is q_2p . k_2r or q_2p+1 . k_2r+1: the foreign half adds
    exact zeros. One select over q, nothing moved."""
    d = q.shape[-1] // n_head
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, q.shape[-1]), 2)
    return jnp.where((lane // d) % 2 == turn, q, jnp.zeros((), q.dtype))


def flash_diff_bthd(q, k, v, n_head, n_kv_head, window=None, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    force=None):
    """The two softmaxes of differential attention (arXiv:2410.05258)
    over grouped heads, causal, through the streamed kernels as they
    are (ISSUE 40). q [B, T, H*D]; k and v [B, T, Hkv*D], Hkv even and
    dividing H. Differential head p of H / 2 is the query pair (q_2p,
    q_2p+1); key/value pair r of Hkv / 2 is (k_2r, k_2r+1) with the
    value v_r = [v_2r; v_2r+1], 2D wide; p reads r = p // (H / Hkv).
    Returns ``(a1, a2)``, [B, T, (H/2)*2D] each: ``a1_p = softmax(q_2p
    k_2r^T scale) v_r``, ``a2_p = softmax(q_2p+1 k_2r+1^T scale) v_r``;
    `scale` defaults to D^-0.5, `window` is flash_bthd's.

    How: a head of D 64 with a value of 128 is, to the kernels, one
    head of 128 whose query has zeros in the lanes of the other key of
    its pair (diff_heads): what a kernel that holds two heads of 64 to
    a 128-lane block does inside for each of its turns (_each_head),
    done here once for each turn of every pair. So each softmax is ONE
    call of grouped-query attention, H / 2 heads of 2D reading Hkv / 2:
    k and v go in as the projections left them, neither repeated nor
    moved, one head to a 128-lane block, the value as wide as the key;
    the MXU's passes are those a head of 64 costs anyway (a contraction
    of 64 half fills it). What it pays is q written twice with half
    its lanes zeroed and the two calls' dq, dk and dv added (XLA; the
    reader ``diff_attn_glue_dev_share_pct`` has their time). The
    kernels trace nothing they did not trace for such a call before;
    each lowering counts under the entry ``diff`` with the widths as
    laid out."""
    d = q.shape[-1] // n_head
    if n_head % 2 or n_kv_head % 2 or n_head % n_kv_head:
        raise ValueError(
            "differential attention pairs its heads: %d query and %d "
            "key/value heads cannot be paired and grouped"
            % (n_head, n_kv_head))
    return tuple(
        _attend(diff_heads(q, n_head, turn), k, v, n_head // 2, True,
                scale or d ** -0.5, block_q, block_k, force, "diff", False,
                n_kv_head // 2, window=window) for turn in (0, 1))


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    force=None):
    """flash_bthd for q/k/v [B, H, T, D]: a wrapper that transposes into
    [B, T, H*D] and the output back. A [B, H, T, D] caller (ring and
    Ulysses attention, the tests; no model) now pays the transposes the
    model used to pay."""
    h = q.shape[1]
    out = _attend(heads_last(q), heads_last(k), heads_last(v), h, causal,
                  scale, block_q, block_k, force, "bhtd", False)
    return heads_first(out, h)


def flash_attention_lse(q, k, v, causal=False, scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        force=None):
    """flash_bthd_lse for q/k/v [B, H, T, D] (out [B, H, T, D], lse
    [B, H, T]), through the same transposes as flash_attention."""
    h = q.shape[1]
    out, lse = _attend(heads_last(q), heads_last(k), heads_last(v), h,
                       causal, scale, block_q, block_k, force, "bhtd", True)
    return heads_first(out, h), lse


# pallas imports placed at the end so a CPU-only environment that never
# takes the kernel path still imports this module (pl/pltpu are needed at
# trace time only)
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
