"""Pallas TPU megakernel: whole networks resident in VMEM, per frame tile.

BinarEye "stores full network models and feature maps and hence requires no
off-chip bandwidth": weights sit in the 259 kB SRAM, feature maps ping-pong
between the west/east 32 kB feature SRAMs, and the only off-chip traffic is
the image in and the label out.  The staged ``InferencePlan`` lost that on
TPU — one ``pallas_call`` per layer means every packed feature map takes an
HBM round trip between stages.  This kernel restores the chip's execution
model in one ``pallas_call``:

* **SRAM image in VMEM.**  All packed conv weight words + int32 comparator
  thresholds + the FC weights for *every* layer enter as VMEM-resident
  operands (constant index maps: fetched once, resident across the grid) —
  the TPU analogue of the weight SRAM contents.  For the worst chip shape
  (cifar9 at S=1) the conv image is 8 x 256x4x8 words = 262 kB, within 1%
  of the chip's 259 kB weight SRAM.  The FC layers run on the MXU as exact
  +/-1 matmuls (integer sums below 2^24 are exact in f32), so their words
  are unpacked to +/-1 bf16 once per dispatch at the kernel boundary.
* **Feature maps stay in VMEM.**  Inter-layer maps are kernel-resident
  values in the lane layout of ``binary_conv2x2_block`` (packed channel
  words on sublanes, positions on lanes, pooling by dilation) and never
  touch HBM.
* **Double-buffered frame streaming.**  The grid iterates frame tiles;
  raw frames stay in HBM (``memory_space=ANY``) in the lane layout
  ``(B, Cin, P)`` — three colour rows of positions, not 3 channels padded
  to 128 lanes — and are streamed tile by tile with manual
  ``make_async_copy``/wait into a 2-slot VMEM buffer, so tile N+1 DMAs in
  while tile N computes; logits DMA out the same way.  The IO thermometer
  encode runs in-kernel on the raw integer pixels, so the only HBM traffic
  of the whole network is frames in, logits out.  Within a tile the frames
  run one at a time through the network (a ``fori_loop``), which bounds
  the live accumulator to one frame's ``F x P`` int32 sums.
* **f-tiled conv.**  Each conv layer's F output neurons are computed in
  chunks of ``ft`` (``ft=0`` = all F in one chunk).  Tiling is a pure
  schedule choice — packed output words concatenate to the identical
  result.  The best ``bb``/``ft`` per (program, backend, batch) comes from
  the persistent autotune cache (``kernels.autotune``); a composite
  accepts one ``ft`` per member *group*, as a tuple in ``member_groups``
  order.
* **Multi-program composite dispatch (sub-array sharing).**  When several
  resident programs' S-modes tile the 256-channel array exactly (4xS4,
  2xS2, 2xS4+1xS2, ...), their weight images pack side-by-side on the F
  axis into ONE composite SRAM image and their frame streams run through
  ONE ``pallas_call`` per batch — the chip's concurrent sub-array
  recombination, not time-interleaved whole-array dispatches.  Each
  member computes on its own disjoint F range (and its own feature maps).

The per-layer arithmetic is ``binary_conv2x2_block.conv_block_body`` — the
staged path's exact function — so all execution modes are bit-exact by
construction (tested, ``tests/test_megakernel.py`` and
``tests/test_composite.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.binarize import PACK_WIDTH
from repro.kernels.binary_conv2x2_block import (LANES, compiler_params,
                                                conv_block_body, lane_map,
                                                lanes_for, neuron_rows,
                                                pack_rows, unlane_map,
                                                unpack_rows, valid_lanes)

# Member stage spec entries (hashable; built by interpreter):
#   ("io",   h, w, cin, bits, channels)
#   ("conv", h, w, c, f, pool, f_off)      h/w = input map size; f_off =
#                                          the member's row offset on the
#                                          composite image's F axis
#   ("fc",   k, n, final, pack_out, n_off) n_off = row offset on the
#                                          composite FC image's N axis
# A composite spec is a tuple of member specs; the solo megakernel is the
# one-member special case (offsets 0), so both paths share one kernel.

_NT = (((1,), (1,)), ((), ()))           # contract the lane axes: A @ B.T


def _solo_member_spec(spec):
    """Lift ``InferencePlan.mega``'s offset-less stage tuples to a
    one-member composite spec (all offsets 0)."""
    return (tuple(st if st[0] == "io" else st + (0,) for st in spec),)


def _f_tiles(f: int, ft: int):
    """Static (offset, length) chunks of the F axis; ft=0 -> one chunk."""
    if not ft or ft >= f:
        return ((0, f),)
    ft = max(PACK_WIDTH, ft // PACK_WIDTH * PACK_WIDTH)
    return tuple((f0, min(ft, f - f0)) for f0 in range(0, f, ft))


def thermometer_lanes(frame, bits: int, cin: int, channels: int):
    """In-kernel IO encode: (cin, P) int32 raw pixels -> (channels//32, P)
    uint32 packed thermometer words — ``binarize.thermometer_pack``'s
    arithmetic (plane i of colour c is -1 exactly when ``x_c < t_i``,
    leftover planes are +1 bias) on the lane layout."""
    per = channels // cin
    levels = 2 ** bits
    i = jax.lax.broadcasted_iota(jnp.int32, (channels, 1), 0)
    xs = jnp.zeros((channels, frame.shape[-1]), jnp.float32)
    k = jnp.zeros((channels, 1), jnp.int32)
    for c in range(cin):
        m = (i >= c * per) & (i < (c + 1) * per)
        xs = jnp.where(m, frame[c:c + 1, :].astype(jnp.float32), xs)
        k = jnp.where(m, i - c * per, k)
    t = (k.astype(jnp.float32) + 0.5) * (levels / per)
    return pack_rows(((xs < t) & (i < cin * per)).astype(jnp.int32))


def _fc_from_map(fm, wt, fc_index: int, row: int, dil: int, hm: int,
                 wm: int):
    """The first FC on a (Fw, P) packed map: (1, Npad) f32 exact sums.

    The FC input is the map's row-major (H, W, F) flatten.  A one-hot
    selection matmul gathers the hm*wm valid positions into rows (the MXU
    does the transpose), and each position's F signs contract against its
    F rows of the +/-1 weight matrix."""
    f = fm.shape[0] * PACK_WIDTH
    xs = (1 - 2 * unpack_rows(fm)).astype(jnp.bfloat16)        # (F, P) +/-1
    lanes = valid_lanes(hm, wm, row, dil)
    rows = -(-len(lanes) // 8) * 8
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    target = jnp.full((rows, 1), -1, jnp.int32)
    for ri, lane in enumerate(lanes):
        target = jnp.where(r == ri, int(lane), target)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (rows, fm.shape[1]), 1)
           == target).astype(jnp.bfloat16)
    t = jax.lax.dot_general(sel, xs, _NT,
                            preferred_element_type=jnp.float32)  # (rows, F)
    s = None
    for ri in range(len(lanes)):
        part = jnp.dot(t[ri:ri + 1, :].astype(jnp.bfloat16),
                       wt[fc_index, ri * f:(ri + 1) * f, :],
                       preferred_element_type=jnp.float32)
        s = part if s is None else s + part
    return s


def _run_fc_tail(fm, wt, fc_stages, row: int, dil: int, hm: int, wm: int):
    """The FC chain of one member on its VMEM-resident map -> (1, Npad)
    int32 logits (the member's classes at its final FC's N offset)."""
    x = None
    for fi, st in enumerate(fc_stages):
        _, k, n, final, _pack_out, n_off = st
        if fi == 0:
            s = _fc_from_map(fm, wt, fi, row, dil, hm, wm)
        else:                  # hidden chain: exact +/-1 row matmul
            s = jnp.dot(x, wt[fi, :k, :], preferred_element_type=jnp.float32)
        if final:
            return s.astype(jnp.int32)
        # sign activation (ties -> +1), the hidden layer's +/-1 outputs
        x = jnp.where(s[:, n_off:n_off + n] < 0, -1.0, 1.0
                      ).astype(jnp.bfloat16)
    raise AssertionError("member spec must end with a final FC stage")


def _split_stages(stages):
    """(io+conv prefix, fc tail) of a member spec."""
    n = sum(1 for st in stages if st[0] != "fc")
    return stages[:n], stages[n:]


def _run_member(frame, img, stages, ft):
    """One member's whole-network pipeline on ONE frame.

    ``frame``: (cin, P) int32 raw pixels in the lane layout; ``img``: the
    (composite) SRAM image refs — ``nr`` (Lc, F, 128) uint32 neuron
    records (``binary_conv2x2_block.neuron_rows``), ``wt`` (Lf, K, Npad)
    +/-1 bf16 — the member reads its own F rows via the spec's static
    offsets.  Returns (1, Npad) int32 logits.
    """
    head, tail = _split_stages(stages)
    ci = 0
    fm = None
    row = dil = hm = wm = 0
    for st in head:
        if st[0] == "io":
            _, h, w, cin, bits, channels = st
            fm = thermometer_lanes(frame, bits, cin, channels)
            row, dil, hm, wm = w, 1, h, w
        else:
            _, h, w, c, f, pool, f_off = st
            cwp = c // PACK_WIDTH
            chunks = [
                conv_block_body(
                    fm, img["nr"].at[ci], k4=4 * c, cw=cwp, ww=img["ww"],
                    row=row, dil=dil, pool=pool, f0=f_off + f0,
                    nw=fl // PACK_WIDTH)
                for f0, fl in _f_tiles(f, ft)]
            fm = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, 0)
            hm, wm = h - 1, w - 1
            if pool:
                hm, wm, dil = hm // 2, wm // 2, dil * 2
            ci += 1
    return _run_fc_tail(fm, img["wt"], tail, row, dil, hm, wm)


def _run_tile(read, write, img, stages, ft, n: int):
    """Run one member over ``n`` frames: ``read(b)`` yields frame b's
    (cin, P) pixels, ``write(b, logits)`` stores its (1, Npad) logits."""
    def body(b, carry):
        write(b, _run_member(read(b), img, stages, ft))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _member_groups(spec):
    """Partition member indices into sub-array groups: members whose
    IO+conv chains are shape-identical (F offsets stripped) share an input
    DMA wait and one f-tile setting."""
    classes = {}
    for m, stages in enumerate(spec):
        head, _ = _split_stages(stages)
        key = tuple(st[:6] for st in head)     # strips the conv f_off
        classes.setdefault(key, []).append(m)
    return tuple(tuple(v) for v in classes.values())


def member_groups(spec):
    """Public alias of :func:`_member_groups`: the composite's sub-array
    groups, in the order per-group tile overrides (``ft`` tuples) index."""
    return _member_groups(spec)


def _group_ft(ft, gi: int) -> int:
    """Resolve the f-tile for member group ``gi``: a plain int applies to
    every group, a tuple carries one entry per group."""
    return ft[gi] if isinstance(ft, tuple) else ft


def _fc_pm1(fw):
    """(Lf, N, Kw) packed FC words -> (Lf, Kw*32, Npad) +/-1 bf16: the
    MXU form of the FC image, K on sublanes and N padded to whole lanes."""
    lf, n, kw = fw.shape
    sh = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    bits = ((fw[..., None] >> sh) & 1).reshape(lf, n, kw * PACK_WIDTH)
    wt = (1 - 2 * bits.astype(jnp.int32)).transpose(0, 2, 1)
    npad = -(-n // LANES) * LANES
    return jnp.pad(wt, ((0, 0), (0, 0), (0, npad - n))).astype(jnp.bfloat16)


def kernel_image(image):
    """The weight image in kernel operand form: (neuron records
    (Lc, F, 128) uint32, +/-1 FC weights (Lf, K, Npad) bf16)."""
    nr = jax.vmap(neuron_rows)(image["cw"], image["ct"], image["cf"])
    return nr, _fc_pm1(image["fw"])


def _img(nr_ref, wt_ref, image_cw_words: int):
    return dict(nr=nr_ref, wt=wt_ref, ww=image_cw_words)


def lane_frames(frames):
    """(B, H, W, Cin) integer frames -> (B, 8, P) int32 lane layout: one
    row of positions per colour, zero rows up to a whole sublane tile (a
    DMA may only slice whole tiles)."""
    b, h, w, cin = frames.shape
    x = frames.astype(jnp.int32).transpose(0, 3, 1, 2).reshape(b, cin, h * w)
    return jnp.pad(x, ((0, 0), (0, (-cin) % 8),
                       (0, lanes_for(h, w) - h * w)))


def _whole(shape):
    """The whole array as one block: fetched once (an input) or written
    back once (an output), VMEM-resident across the grid."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _n=nd: (0,) * _n)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole array, scalar access


def _final_cols(stages):
    """(n_off, classes) of a member's final FC."""
    st = stages[-1]
    assert st[0] == "fc" and st[3], stages
    return st[5], st[2]


def _composite_kernel(*refs, spec, bb: int, n_tiles: int, ft: int,
                      ww: int):
    """One frame-tile grid step: per-member 2-slot input/output DMA
    pipelining around the multi-member compute."""
    nm = len(spec)
    frames_hbm = refs[:nm]
    img = _img(*refs[nm:nm + 2], ww)
    out_hbm = refs[nm + 2:nm + 2 + nm]
    sc = refs[nm + 2 + nm:]
    fbuf, obuf = sc[:nm], sc[nm:2 * nm]
    in_sem, out_sem = sc[2 * nm:3 * nm], sc[3 * nm:4 * nm]

    i = pl.program_id(0)
    slot = jax.lax.rem(i, 2)
    nxt = jax.lax.rem(i + 1, 2)

    def in_copy(p, s, t):
        return pltpu.make_async_copy(
            frames_hbm[p].at[pl.ds(t * bb, bb)], fbuf[p].at[s],
            in_sem[p].at[s])

    def out_copy(p, s, t):
        return pltpu.make_async_copy(
            obuf[p].at[s], out_hbm[p].at[pl.ds(t * bb, bb)], out_sem[p].at[s])

    @pl.when(i == 0)                     # warm-up: every member's tile 0
    def _():
        for p in range(nm):
            in_copy(p, 0, 0).start()

    @pl.when(i + 1 < n_tiles)            # tile N+1 streams while N computes
    def _():
        for p in range(nm):
            in_copy(p, nxt, jnp.minimum(i + 1, n_tiles - 1)).start()

    if n_tiles > 2:                      # drain the DMA issued 2 tiles ago
        @pl.when(i >= 2)                 # before reusing its slot
        def _():
            for p in range(nm):
                out_copy(p, slot, jnp.maximum(i - 2, 0)).wait()

    # input waits are issued per member group, right before that group's
    # compute: member group k+1's DMA keeps streaming while group k
    # convolves — the chip's IO-pads-during-CONV overlap, per sub-array —
    # instead of a barrier on every member's copy up front.
    for gi, group in enumerate(_member_groups(spec)):
        for p in group:
            in_copy(p, slot, i).wait()
        for p in group:
            def read(b, _p=p):
                return fbuf[_p][slot, b]

            def write(b, lg, _p=p):
                obuf[_p][slot, pl.ds(b, 1), :] = lg

            _run_tile(read, write, img, spec[p], _group_ft(ft, gi), bb)
    for p in range(nm):
        out_copy(p, slot, i).start()

    @pl.when(i == n_tiles - 1)           # final tile: drain everything
    def _():
        for p in range(nm):
            out_copy(p, slot, i).wait()
    if n_tiles > 1:
        @pl.when(i == n_tiles - 1)
        def _():
            for p in range(nm):
                out_copy(p, 1 - slot, i - 1).wait()


@functools.partial(jax.jit, static_argnames=("spec", "bb", "ft", "interpret",
                                             "name"))
def composite_forward(image, frames, *, spec, bb: int = 8, ft=0,
                      interpret: bool = False, name: str = "composite"):
    """Multi-program packed inference in a single resident ``pallas_call``.

    image:  the composite weight image (``interpreter.pack_programs``) —
            or a member's own image for the one-member (solo) case:
            ``cw`` (Lc, F_total, 4, Cw) uint32 conv words, ``ct``/``cf``
            (Lc, F_total) int32 thresholds/directions, ``fw``
            (Lf, N_total, Kw) uint32 padded FC words.
    frames: tuple of (B_m, H_m, W_m, Cin_m) integer images, one per
            member; ragged B_m are padded to the longest member's batch
            (padding frames compute garbage that is trimmed on return).
    spec:   static tuple of member stage specs (see module header).
    bb:     frame-tile size (the double-buffered streaming granule).
    ft:     conv f-tile size; 0 = all F per chunk.  A tuple carries one
            f-tile per *member group* (``member_groups(spec)`` order) —
            groups with different sub-array widths tune separately.
    name:   the kernel's name in the compiled program and the profiler
            (``megakernel`` for the one-member case).
    Returns a tuple of (B_m, classes_m) int32 logits, one per member.
    """
    assert len(frames) == len(spec), (len(frames), len(spec))
    if isinstance(ft, tuple):
        n_groups = len(_member_groups(spec))
        if len(ft) != n_groups:
            raise ValueError(
                f"per-group ft {ft} carries {len(ft)} entries for "
                f"{n_groups} member groups")
    bs = [f.shape[0] for f in frames]
    bmax = max(bs)
    bb = max(1, min(bb, bmax))
    bpad = -(-bmax // bb) * bb
    n_tiles = bpad // bb

    padded = []
    for f in frames:
        f = lane_frames(f)
        if f.shape[0] != bpad:
            f = jnp.pad(f, ((0, bpad - f.shape[0]), (0, 0), (0, 0)))
        padded.append(f)

    ops = kernel_image(image)
    npad = ops[1].shape[-1]
    nm = len(spec)
    outs = pl.pallas_call(
        functools.partial(_composite_kernel, spec=spec, bb=bb,
                          n_tiles=n_tiles, ft=ft, ww=image["cw"].shape[-1]),
        grid=(n_tiles,),
        in_specs=(
            [pl.BlockSpec(memory_space=pl.ANY)] * nm      # frames: HBM
            + [_whole(a.shape) for a in ops]),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nm,
        out_shape=[jax.ShapeDtypeStruct((bpad, npad), jnp.int32)] * nm,
        scratch_shapes=(
            [pltpu.VMEM((2, bb) + f.shape[1:], jnp.int32) for f in padded]
            + [pltpu.VMEM((2, bb, npad), jnp.int32) for _ in range(nm)]
            + [pltpu.SemaphoreType.DMA((2,)) for _ in range(2 * nm)]),
        compiler_params=compiler_params(),
        interpret=interpret,
        name=name,
    )(*padded, *ops)
    outs = outs if isinstance(outs, (list, tuple)) else [outs]
    res = []
    for o, b, stages in zip(outs, bs, spec):
        off, n = _final_cols(stages)
        res.append(o[:b, off:off + n])
    return tuple(res)


# ---------------------------------------------------------------------------
# In-kernel conditional cascade: detector -> escalation queue -> recognizer
# ---------------------------------------------------------------------------

def _member_ft(ft, spec, m: int):
    """Member ``m``'s conv f-tile: a plain int applies everywhere, a
    tuple carries one entry per member *group* (``member_groups`` order)."""
    if not isinstance(ft, tuple):
        return ft
    for gi, group in enumerate(_member_groups(spec)):
        if m in group:
            return ft[gi]
    raise AssertionError(f"member {m} not in any group of {spec}")


def bounded_drain_loop(cond_fun, chunk_fun, n_chunks: int,
                       check_every: int = 1) -> None:
    """Drain up to ``n_chunks`` work chunks, re-checking the live
    condition every ``check_every`` chunks — the while_loop-with-a-
    limited-cond idiom made jittable: the trip count is static
    (``n_chunks`` bounds the queue), early exit is a *predicated skip*
    rather than a data-dependent trip count, and the condition is
    evaluated once per chunk group instead of once per chunk (the
    ``k``-step re-check that amortizes the cond when chunks are cheap).

    ``cond_fun(g0)`` must return a scalar bool — "is there still work at
    or beyond chunk ``g0``" — and ``chunk_fun(c)`` performs chunk ``c``'s
    effects (ref stores, DMA); both run *inside* a Pallas kernel: the
    group skip lowers to ``pl.when`` and the intra-group sweep to a
    ``lax.fori_loop``, so a drained queue skips whole groups of
    recognizer work at trace-free runtime cost.
    """
    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    for g0 in range(0, n_chunks, check_every):
        n = min(check_every, n_chunks - g0)

        @pl.when(cond_fun(g0))
        def _(g0=g0, n=n):
            jax.lax.fori_loop(0, n,
                              lambda k, c: (chunk_fun(g0 + k), c)[1], 0)


def _compact(mask, queue, count, vbuf, sbuf, sem, base, n_real):
    """Order-preserving compaction of a tile's flagged frames into the
    queue: frame ``base + j`` lands at queue row ``count[0]`` + (# flagged
    before it in this tile); frames at or past ``n_real`` never enter.

    ``mask`` is a (bb, 1) int32 vector; queue (SMEM) indices must be
    scalars, so the mask turns into a row (a masked sublane sum) and one
    DMA carries it from VMEM scratch ``vbuf`` to SMEM scratch ``sbuf``,
    where a scalar loop walks it."""
    bb, lanes = mask.shape[0], vbuf.shape[1]
    r = jax.lax.broadcasted_iota(jnp.int32, (bb, lanes), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bb, lanes), 1)
    row = jnp.sum(jnp.where(r == c, mask, 0), axis=0, keepdims=True)
    vbuf[...] = jnp.broadcast_to(row, vbuf.shape)
    cp = pltpu.make_async_copy(vbuf, sbuf, sem)
    cp.start()
    cp.wait()

    def flag(j, cnt):
        g = base + j
        hit = jnp.logical_and(sbuf[0, j] > 0, g < n_real)

        @pl.when(hit)
        def _():
            queue[cnt] = g

        return cnt + hit.astype(jnp.int32)

    count[0] = jax.lax.fori_loop(0, bb, flag, count[0])


def _queue_scratch(bb: int):
    """VMEM row + SMEM mirror + DMA semaphore for :func:`_compact`."""
    lanes = -(-bb // LANES) * LANES
    return [pltpu.VMEM((8, lanes), jnp.int32),
            pltpu.SMEM((8, lanes), jnp.int32),
            pltpu.SemaphoreType.DMA(())]


def _zero_smem(ref, n: int) -> None:
    def body(k, carry):
        ref[k] = jnp.int32(0)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _cascade_kernel(frames_hbm, ctrl_ref, nr_ref, wt_ref,
                    det_out, rec_out, queue, count,
                    fbuf, gbuf, in_sem, g_sem, vbuf, sbuf, q_sem,
                    *, spec, bb: int, rb: int, bpad: int,
                    check_every: int, positive_class: int, ft, ww: int):
    """One grid step of the fused detector->recognizer cascade.

    Grid = (n_det_tiles + 1,): every step but the last streams one
    detector frame tile (2-slot double-buffered DMA, exactly the
    composite kernel's pipeline), runs the detector member, writes its
    logits, and *escalates in-kernel* — the integer logit margin
    (positive-class logit minus the best competitor) is compared against
    the ``ctrl`` threshold and winning frame indices are compacted into
    the SMEM escalation ``queue`` (count[0] = queue depth).  The
    final step drains the queue through the recognizer member in chunks
    of ``rb`` via :func:`bounded_drain_loop`: each live chunk gathers
    its frames from HBM by queue index (per-lane dynamic-slice DMA),
    runs the recognizer, and stores logits at the chunk's queue rows
    (compacted layout: recognizer row k answers queue entry k).
    count[1] counts recognizer frame slots actually computed — the
    energy bill's escalated + chunk-padding figure, reported back to the
    host as a scalar output.
    """
    n_det = bpad // bb
    n_chunks = -(-bpad // rb)
    det_spec, rec_spec = spec
    img = _img(nr_ref, wt_ref, ww)
    d_off, ncd = _final_cols(det_spec)
    i = pl.program_id(0)
    slot = jax.lax.rem(i, 2)
    nxt = jax.lax.rem(i + 1, 2)

    def in_copy(s, t):
        return pltpu.make_async_copy(frames_hbm.at[pl.ds(t * bb, bb)],
                                     fbuf.at[s], in_sem.at[s])

    @pl.when(i == 0)                     # init + warm-up DMA for tile 0
    def _():
        _zero_smem(count, 2)
        _zero_smem(queue, bpad)
        rec_out[...] = jnp.zeros_like(rec_out)
        in_copy(0, 0).start()

    @pl.when(i + 1 < n_det)              # tile N+1 streams while N computes
    def _():
        in_copy(nxt, i + 1).start()

    thr = ctrl_ref[0, 0]
    n_real = ctrl_ref[0, 1]

    @pl.when(i < n_det)                  # detector phase: one frame tile
    def _():
        in_copy(slot, i).wait()

        def write(b, lg):
            det_out[pl.ds(i * bb + b, 1), :] = lg

        _run_tile(lambda b: fbuf[slot, b], write, img, det_spec,
                  _member_ft(ft, spec, 0), bb)
        logits = det_out[pl.ds(i * bb, bb), :][:, d_off:d_off + ncd]
        # escalation mask: integer margin vs the pre-ceiled threshold
        # (m >= ceil(margin) <=> m >= margin for integer m); padding
        # lanes (global index >= n_real) never escalate
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        pos = logits[:, positive_class:positive_class + 1]
        rest = jnp.max(jnp.where(col == positive_class,
                                 jnp.iinfo(jnp.int32).min, logits),
                       axis=1, keepdims=True)
        _compact((pos - rest >= thr).astype(jnp.int32), queue, count,
                 vbuf, sbuf, q_sem, i * bb, n_real)

    @pl.when(i == n_det)                 # recognizer phase: drain the queue
    def _():
        total = count[0]

        def chunk(c):
            # ragged tail clamps into range; the overlapped rows are
            # recomputed idempotently (same queue entries, same logits)
            base = jnp.minimum(c * rb, bpad - rb)
            copies = [pltpu.make_async_copy(
                frames_hbm.at[pl.ds(queue[base + j], 1)],
                gbuf.at[pl.ds(j, 1)], g_sem.at[j]) for j in range(rb)]
            for cp in copies:            # gather rb frames by queue index
                cp.start()
            for cp in copies:
                cp.wait()

            def write(b, lg):
                rec_out[pl.ds(base + b, 1), :] = lg

            _run_tile(lambda b: gbuf[b], write, img, rec_spec,
                      _member_ft(ft, spec, 1), rb)
            count[1] = count[1] + rb     # slots computed = the bill

        bounded_drain_loop(lambda g0: g0 * rb < total, chunk,
                           n_chunks, check_every)


@functools.partial(jax.jit, static_argnames=(
    "spec", "bb", "rb", "ft", "check_every", "positive_class", "interpret"))
def cascade_forward(image, frames: jax.Array, ctrl, *, spec,
                    bb: int = 8, rb: int = 0, ft=0, check_every: int = 1,
                    positive_class: int = 1, interpret: bool = False):
    """Fused two-stage cascade in ONE resident ``pallas_call``.

    image:  the det+rec composite weight image
            (``interpreter.pack_cascade``) — both stages' SRAM contents
            VMEM-resident for the whole dispatch.
    frames: (B, H, W, Cin) integer images — ONE stream; the detector
            sees every frame, the recognizer only the frames the kernel
            itself escalates.
    ctrl:   (1, 2) int32 ``[threshold, n_real]`` — the escalation
            threshold on the integer logit margin (host float margins
            pre-ceiled by ``CascadePlan.margin_ctrl``; dynamic, so
            margin sweeps and ragged batches never retrace) and the
            count of real (non-padding) frames.
    spec:   static 2-member composite spec, detector first.
    bb/ft:  detector frame-tile / conv f-tile sizes (``ft`` may be a
            per-group tuple, ``member_groups`` order).
    rb:     recognizer chunk size (0 = ``bb``): escalated frames drain
            through the recognizer ``rb`` at a time.
    check_every: drain-loop condition re-check period, in chunks
            (:func:`bounded_drain_loop`).

    Returns ``(det_logits (B, Cd), rec_logits (B, Cr), queue (B,),
    counts (2,))`` — all int32.  ``counts[0]`` is the escalated count E;
    ``queue[:E]`` holds the escalated frame indices in ascending order
    and ``rec_logits[k]`` answers frame ``queue[k]`` (compacted layout;
    rows >= E are zeros/garbage).  ``counts[1]`` is the number of
    recognizer frame slots computed (>= E: chunk padding) — the
    recognizer-stage energy bill.
    """
    if len(spec) != 2:
        raise ValueError(f"cascade spec needs exactly 2 members (detector, "
                         f"recognizer), got {len(spec)}")
    det_spec, rec_spec = spec
    assert det_spec[0][0] == "io", det_spec
    d_off, ncd = _final_cols(det_spec)
    r_off, ncr = _final_cols(rec_spec)
    if ncd < 2:
        raise ValueError(f"detector needs >= 2 classes, got {ncd}")
    if not 0 <= positive_class < ncd:
        raise ValueError(f"positive_class {positive_class} out of range for "
                         f"{ncd} detector classes")
    b = frames.shape[0]
    bb = max(1, min(bb, b))
    bpad = -(-b // bb) * bb
    n_det = bpad // bb
    rb = max(1, min(rb if rb else bb, bpad))

    frames = lane_frames(frames)
    if frames.shape[0] != bpad:
        frames = jnp.pad(frames, ((0, bpad - b), (0, 0), (0, 0)))
    ctrl = jnp.asarray(ctrl, jnp.int32).reshape(1, 2)
    ops = kernel_image(image)
    npad = ops[1].shape[-1]

    det, rec, qout, cnt = pl.pallas_call(
        functools.partial(_cascade_kernel, spec=spec, bb=bb, rb=rb,
                          bpad=bpad, check_every=check_every,
                          positive_class=positive_class, ft=ft,
                          ww=image["cw"].shape[-1]),
        grid=(n_det + 1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # frames: HBM
                  _SMEM] + [_whole(a.shape) for a in ops],
        out_specs=[_whole((bpad, npad)), _whole((bpad, npad)),
                   _SMEM, _SMEM],
        out_shape=[jax.ShapeDtypeStruct((bpad, npad), jnp.int32),
                   jax.ShapeDtypeStruct((bpad, npad), jnp.int32),
                   jax.ShapeDtypeStruct((bpad,), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((2, bb) + frames.shape[1:], jnp.int32),
                        pltpu.VMEM((rb,) + frames.shape[1:], jnp.int32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((rb,))]
                       + _queue_scratch(bb),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="cascade",
    )(frames, ctrl, *ops)
    return (det[:b, d_off:d_off + ncd], rec[:b, r_off:r_off + ncr],
            qout[:b], cnt)


# ---------------------------------------------------------------------------
# In-kernel frame-delta gating: popcount gate -> change queue -> recompute
# ---------------------------------------------------------------------------

def _delta_kernel(frames_hbm, ctrl_ref, last_ref, llog_ref, nr_ref, wt_ref,
                  log_out, last_out, queue, count, delta_out,
                  fbuf, gbuf, in_sem, g_sem, vbuf, sbuf, q_sem,
                  *, spec, bb: int, rb: int, bpad: int,
                  check_every: int, ft, ww: int):
    """One grid step of the delta-gated megakernel.

    Grid = (n_tiles + 1,): every step but the last streams one frame
    tile (the cascade kernel's 2-slot double-buffered DMA), thermometer-
    packs it in-kernel, and computes the packed Hamming distance against
    the resident last-frame words (``popcount(cur XOR ref)`` summed over
    the map's positions — the same integer domain the conv kernel works
    in).  Lanes whose delta reaches the ``ctrl`` threshold are *changed*:
    their indices compact into the VMEM ``queue`` (order-preserving,
    exactly the cascade's escalation compaction) and their last-frame
    words advance to the current frame; unchanged lanes keep their
    reference words, so drift never accumulates while a lane coasts.  The
    final step drains the queue through the network in chunks of ``rb``
    (:func:`bounded_drain_loop`), scattering fresh logits into an output
    that was *initialized from the resident last-logits buffer* — skipped
    lanes therefore emit their cached logits and the merged output doubles
    as the next step's last-logits state.  count[0] = changed count,
    count[1] = frame slots actually computed (the energy bill's
    recompute + chunk-padding figure).
    """
    (member,) = spec
    _, h, w, cin, bits, channels = member[0]
    img = _img(nr_ref, wt_ref, ww)
    n_tiles = bpad // bb
    n_chunks = -(-bpad // rb)
    i = pl.program_id(0)
    slot = jax.lax.rem(i, 2)
    nxt = jax.lax.rem(i + 1, 2)

    def in_copy(s, t):
        return pltpu.make_async_copy(frames_hbm.at[pl.ds(t * bb, bb)],
                                     fbuf.at[s], in_sem.at[s])

    @pl.when(i == 0)                     # init + warm-up DMA for tile 0
    def _():
        _zero_smem(count, 2)
        _zero_smem(queue, bpad)
        log_out[...] = llog_ref[...]     # skipped lanes -> cached logits
        in_copy(0, 0).start()

    @pl.when(i + 1 < n_tiles)            # tile N+1 streams while N gates
    def _():
        in_copy(nxt, i + 1).start()

    thr = ctrl_ref[0, 0]
    n_real = ctrl_ref[0, 1]

    @pl.when(i < n_tiles)                # gate phase: one frame tile
    def _():
        in_copy(slot, i).wait()
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, fbuf.shape[-1]), 1)
                 < h * w)                # padding lanes carry no pixels

        def gate(b, carry):
            g = i * bb + b
            cur = thermometer_lanes(fbuf[slot, b], bits, cin, channels)
            ref = last_ref[g]
            pc = jnp.where(valid, jax.lax.population_count(cur ^ ref), 0)
            d = jnp.sum(jnp.sum(pc.astype(jnp.int32), axis=1, keepdims=True),
                        axis=0, keepdims=True)                    # (1, 1)
            live = g < n_real
            delta_out[pl.ds(g, 1), :] = jnp.where(live, d, 0)
            # the reference advances ONLY on recompute: a coasting lane's
            # delta stays measured against the frame that produced its
            # cached logits, so sub-threshold drift cannot accumulate
            last_out[g] = jnp.where((d >= thr) & live, cur, ref)
            return carry

        jax.lax.fori_loop(0, bb, gate, 0)
        d = delta_out[pl.ds(i * bb, bb), :]
        _compact((d >= thr).astype(jnp.int32), queue, count,
                 vbuf, sbuf, q_sem, i * bb, n_real)

    @pl.when(i == n_tiles)               # recompute phase: drain the queue
    def _():
        total = count[0]

        def chunk(c):
            # ragged tail clamps into range; overlapped rows recompute
            # idempotently (same queue entries, same scatter targets)
            base = jnp.minimum(c * rb, bpad - rb)
            copies = [pltpu.make_async_copy(
                frames_hbm.at[pl.ds(queue[base + j], 1)],
                gbuf.at[pl.ds(j, 1)], g_sem.at[j]) for j in range(rb)]
            for cp in copies:            # gather rb frames by queue index
                cp.start()
            for cp in copies:
                cp.wait()

            def write(b, lg):            # scatter fresh logits by index
                log_out[pl.ds(queue[base + b], 1), :] = lg

            _run_tile(lambda b: gbuf[b], write, img, member,
                      _member_ft(ft, spec, 0), rb)
            count[1] = count[1] + rb     # slots computed = the bill

        bounded_drain_loop(lambda g0: g0 * rb < total, chunk,
                           n_chunks, check_every)


@functools.partial(jax.jit, static_argnames=(
    "spec", "bb", "rb", "ft", "check_every", "interpret"))
def delta_forward(image, frames: jax.Array, last, llog, ctrl, *, spec,
                  bb: int = 8, rb: int = 0, ft=0, check_every: int = 1,
                  interpret: bool = False):
    """Delta-gated whole-network inference in ONE resident ``pallas_call``.

    image:  the program's weight image (``interpreter.pack_delta`` /
            ``fold_params(..., image=True)``), VMEM-resident throughout.
    frames: (B, H, W, Cin) integer images — batch slot b is *stream* b
            of an always-on deployment; one call advances every stream
            by one time step.
    last:   (B, H, W, channels//32) uint32 — each stream's resident
            last-frame words (the packed thermometer encoding of the
            frame that produced its cached logits).
    llog:   (B, classes) int32 — each stream's cached logits.
    ctrl:   (1, 2) int32 ``[threshold, n_real]`` (build with
            ``DeltaPlan.delta_ctrl``): the change threshold on the packed
            Hamming distance (dynamic — threshold sweeps never retrace)
            and the count of real (non-padding) streams.
    spec:   static 1-member composite spec.
    bb/ft:  frame-tile / conv f-tile sizes.
    rb:     recompute chunk size (0 = ``bb``): changed frames drain
            through the network ``rb`` at a time.
    check_every: drain-loop condition re-check period, in chunks.

    Returns ``(logits (B, C), new_last (B, H, W, Cw), queue (B,),
    counts (2,), deltas (B,))``.  ``logits`` merges fresh logits for
    changed lanes with cached logits for skipped lanes — it is also the
    next call's ``llog``.  ``new_last`` is the next call's ``last``.
    ``counts[0]`` is the changed count K; ``queue[:K]`` holds the changed
    frame indices ascending.  ``counts[1]`` is the number of frame slots
    computed (>= K: chunk padding) — the recompute energy bill.
    ``deltas`` are the per-lane packed Hamming distances (0 for padding).
    """
    if len(spec) != 1:
        raise ValueError(
            f"delta spec needs exactly 1 member, got {len(spec)}")
    (member,) = spec
    io = member[0]
    assert io[0] == "io", member
    h, w, cin, bits, channels = io[1], io[2], io[3], io[4], io[5]
    cpw = channels // PACK_WIDTH
    n_off, ncls = _final_cols(member)

    b = frames.shape[0]
    bb = max(1, min(bb, b))
    bpad = -(-b // bb) * bb
    n_tiles = bpad // bb
    rb = max(1, min(rb if rb else bb, bpad))

    if last.shape != (b, h, w, cpw):
        raise ValueError(f"last-frame state must be {(b, h, w, cpw)}, "
                         f"got {last.shape}")
    if llog.shape != (b, ncls):
        raise ValueError(f"last-logits state must be {(b, ncls)}, "
                         f"got {llog.shape}")
    ops = kernel_image(image)
    npad = ops[1].shape[-1]
    frames = lane_frames(frames)
    last = lane_map(jnp.asarray(last, jnp.uint32))
    llog = jnp.pad(jnp.asarray(llog, jnp.int32),
                   ((0, bpad - b), (n_off, npad - n_off - ncls)))
    if bpad != b:
        frames = jnp.pad(frames, ((0, bpad - b), (0, 0), (0, 0)))
        last = jnp.pad(last, ((0, bpad - b), (0, 0), (0, 0)))
    ctrl = jnp.asarray(ctrl, jnp.int32).reshape(1, 2)

    logits, new_last, qout, cnt, deltas = pl.pallas_call(
        functools.partial(_delta_kernel, spec=spec, bb=bb, rb=rb,
                          bpad=bpad, check_every=check_every, ft=ft,
                          ww=image["cw"].shape[-1]),
        grid=(n_tiles + 1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # frames: HBM
                  _SMEM, _whole(last.shape), _whole(llog.shape)]
                 + [_whole(a.shape) for a in ops],
        out_specs=[_whole((bpad, npad)), _whole(last.shape),
                   _SMEM, _SMEM, _whole((bpad, 1))],
        out_shape=[jax.ShapeDtypeStruct((bpad, npad), jnp.int32),
                   jax.ShapeDtypeStruct(last.shape, jnp.uint32),
                   jax.ShapeDtypeStruct((bpad,), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32),
                   jax.ShapeDtypeStruct((bpad, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((2, bb) + frames.shape[1:], jnp.int32),
                        pltpu.VMEM((rb,) + frames.shape[1:], jnp.int32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((rb,))]
                       + _queue_scratch(bb),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="delta_gate",
    )(frames, ctrl, last, llog, *ops)
    new_last = unlane_map(new_last[:b], h, w, w, 1)
    return (logits[:b, n_off:n_off + ncls], new_last, qout[:b], cnt,
            deltas[:b, 0])


def megakernel_forward(image, frames: jax.Array, *, spec,
                       bb: int = 8, ft: int = 0,
                       interpret: bool = False) -> jax.Array:
    """Whole-network packed inference for ONE program: the one-member
    composite (see :func:`composite_forward`).

    image:  the weight-image artifact (``interpreter.fold_params(...,
            image=True)``).
    frames: (B, H, W, Cin) integer images.
    spec:   static stage tuple from ``InferencePlan.mega``.
    bb/ft:  frame-tile / conv f-tile sizes (tuned values come from the
            ``kernels.autotune`` cache via the interpreter layer).
    Returns (B, classes) int32 logits.
    """
    return composite_forward(image, (frames,), spec=_solo_member_spec(spec),
                             bb=bb, ft=ft, interpret=interpret,
                             name="megakernel")[0]
