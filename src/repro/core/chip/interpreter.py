"""Program interpreter: compiles a BinarEye ISA program into jit-able JAX fns.

Two modes mirror the chip's lifecycle:

* ``forward_train``  — BinaryNet training semantics (1st level of
  flexibility: reprogrammable weights).  Latent float weights, STE sign,
  BatchNorm before the sign activation.  Differentiable end to end.
* ``forward_infer``  — deployment semantics.  BN folded into the per-neuron
  integer threshold comparator; weights/activations are hard +/-1; the
  compute can run through the packed Pallas pipeline (``use_kernels=True``)
  or the float reference path.  Both paths must agree bit-exactly (tested).

Deployment is organized around :class:`InferencePlan` — the program's
geometry is resolved *once* at build time into a static pipeline of fused
packed stages, mirroring how the chip's controller walks its 16-slot
program memory.  The plan consumes the packed deployment artifact from
``fold_params(..., packed=True)``: uint32 weight words plus int32
comparator thresholds, exactly what the silicon's SRAMs hold.  At run
time feature maps stay bit-packed end to end — a single pack at the IO
thermometer encoding, fused conv->threshold->pool->repack per CNN layer
(``binary_conv2x2_block``), fused sign+pack hidden FCs
(``xnor_matmul(pack_out=True)``), and a single unpack-free int32 readout
at the final FC logits.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import binarize
from repro.core.chip import isa, neuron_array as na
from repro.kernels import autotune
from repro.kernels import ops as kops

BN_EPS = 1e-4
BN_MOMENTUM = 0.9


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, program: isa.Program) -> Dict[str, Any]:
    """Latent float params for every instruction (Glorot-ish on latents)."""
    isa.validate(program)
    convs, fcs = [], []
    for (ins, in_h, in_w, in_c, *_rest) in isa.layer_geometry(program):
        if isinstance(ins, isa.ConvInstr):
            key, k1 = jax.random.split(key)
            fan_in = 4 * in_c
            w = jax.random.normal(k1, (ins.features, 2, 2, in_c)) / jnp.sqrt(fan_in)
            convs.append(dict(
                w=w,
                gamma=jnp.ones((ins.features,)),
                beta=jnp.zeros((ins.features,)),
                mean=jnp.zeros((ins.features,)),
                var=jnp.ones((ins.features,)),
            ))
        elif isinstance(ins, isa.FCInstr):
            key, k1 = jax.random.split(key)
            w = jax.random.normal(k1, (ins.out_features, ins.in_features))
            w = w / jnp.sqrt(ins.in_features)
            fcs.append(dict(w=w))
    return {"conv": convs, "fc": fcs}


# ---------------------------------------------------------------------------
# Training-mode forward (STE + BatchNorm)
# ---------------------------------------------------------------------------

def forward_train(params, program: isa.Program, images: jax.Array,
                  train: bool = True):
    """Returns (logits, new_params) — new_params carries updated BN stats."""
    new_conv = []
    ci = fi = 0
    x = None
    for ins in program.instrs:
        if isinstance(ins, isa.IOInstr):
            x = na.thermometer_encode(images, ins.bits, ins.channels)
        elif isinstance(ins, isa.ConvInstr):
            p = params["conv"][ci]
            wb = binarize.ste_sign(p["w"])
            s = na.conv2x2(x, wb)                      # (B, H-1, W-1, F) ints
            if train:
                mean = jnp.mean(s, axis=(0, 1, 2))
                var = jnp.var(s, axis=(0, 1, 2))
                new_p = dict(p)
                new_p["mean"] = BN_MOMENTUM * p["mean"] + (1 - BN_MOMENTUM) * mean
                new_p["var"] = BN_MOMENTUM * p["var"] + (1 - BN_MOMENTUM) * var
                new_conv.append(new_p)
            else:
                mean, var = p["mean"], p["var"]
                new_conv.append(p)
            bn = p["gamma"] * (s - mean) * jax.lax.rsqrt(var + BN_EPS) + p["beta"]
            x = binarize.ste_sign(bn)
            if ins.maxpool:
                x = na.maxpool2x2(x)
            ci += 1
        elif isinstance(ins, isa.FCInstr):
            if x.ndim == 4:
                x = x.reshape(x.shape[0], -1)
            p = params["fc"][fi]
            wb = binarize.ste_sign(p["w"])
            s = na.fc(x, wb)
            if ins.final:
                x = s                                   # integer logits
            else:
                x = binarize.ste_sign(s)
            fi += 1
    return x, {"conv": new_conv, "fc": params["fc"]}


# ---------------------------------------------------------------------------
# Inference-mode forward (folded thresholds, optional Pallas kernels)
# ---------------------------------------------------------------------------

def fold_params(params, program: isa.Program, *, packed: bool = False,
                image: bool = False):
    """Fold BN into comparator thresholds (what the chip stores).

    With ``packed=False`` (default) returns the float-domain folded form:
    +/-1 weight tensors plus float ``tau``/``flip`` per conv — the
    reference the packed path is tested bit-exact against.  With
    ``packed=True`` returns the deployment artifact consumed by
    :class:`InferencePlan` (see :func:`pack_folded` for the layout).
    With ``image=True`` (implies packed) returns the contiguous
    weight-image artifact the whole-network megakernel holds VMEM-resident
    — the SRAM image (see :func:`build_weight_image`).
    """
    folded_convs = []
    for p in params["conv"]:
        tau, flip = binarize.fold_bn_to_threshold(
            p["gamma"], p["beta"], p["mean"], p["var"], eps=BN_EPS)
        folded_convs.append(dict(w=binarize.hard_sign(p["w"]), tau=tau, flip=flip))
    fcs = [dict(w=binarize.hard_sign(p["w"])) for p in params["fc"]]
    folded = {"conv": folded_convs, "fc": fcs}
    if image:
        return build_weight_image(pack_folded(folded), program)
    return pack_folded(folded) if packed else folded


def pack_folded(folded) -> Dict[str, Any]:
    """Bit-pack a float-domain folded artifact into the deployment form.

    Layout (the TPU analogue of the chip's SRAM contents):
      conv[i]["w_words"]: (F, 4, ceil(C/32)) uint32 — taps (dy, dx)
          row-major, channels packed LSB-first (bit=1 encodes -1);
      conv[i]["tau"]:     (F,) int32 integer comparator thresholds
          (``s >= tau`` fires; the ceil of the folded float threshold);
      conv[i]["flip"]:    (F,) int32 comparator direction (gamma < 0);
      fc[i]["w_words"]:   (N, ceil(K/32)) uint32, K packed in the
          row-major flatten order of the preceding (H, W, F) map.
    """
    convs = []
    for p in folded["conv"]:
        f, _, _, c = p["w"].shape
        convs.append(dict(
            w_words=binarize.pack_signs(p["w"].reshape(f, 4, c), axis=-1),
            tau=binarize.threshold_to_int(p["tau"]),
            flip=p["flip"].astype(jnp.int32)))
    fcs = [dict(w_words=binarize.pack_signs(p["w"], axis=-1))
           for p in folded["fc"]]
    return {"conv": convs, "fc": fcs}


def _is_packed_artifact(folded) -> bool:
    stages = list(folded["conv"]) + list(folded["fc"])
    return bool(stages) and "w_words" in stages[0]


def _is_image_artifact(artifact) -> bool:
    return isinstance(artifact, dict) and "cw" in artifact and "fw" in artifact


def ensure_packed(artifact):
    """Admission helper: accept either artifact form, return the packed one.

    The public seam for consumers outside this module (the serving layer
    admits both float-folded and packed artifacts).
    """
    if _is_image_artifact(artifact):
        raise TypeError(
            "weight-image artifact cannot be unstacked back to the packed "
            "per-layer form; fold with packed=True (or keep both)")
    return artifact if _is_packed_artifact(artifact) else pack_folded(artifact)


def build_weight_image(packed, program: isa.Program) -> Dict[str, Any]:
    """Stack a packed per-layer artifact into one contiguous weight image.

    The megakernel's VMEM-resident operand set — the TPU analogue of the
    chip's weight/FC SRAM contents, loaded once and resident while frames
    stream:

      ``cw``: (n_conv, F, 4, Cw) uint32 conv weight words (every conv in a
          valid program has F = C = 256/S, so the stack is rectangular);
      ``ct``/``cf``: (n_conv, F) int32 comparator thresholds / directions;
      ``fw``: (n_fc, N_max, Kw_max) uint32 FC weight words, zero-padded to
          the widest layer (zero words encode +1 and are never read: the
          kernel slices each layer's true (N, Kw) statically).
    """
    isa.validate(program)
    f = isa.ARRAY_CHANNELS // program.s
    cww = f // binarize.PACK_WIDTH
    convs = packed["conv"]
    if convs:
        cw = jnp.stack([p["w_words"] for p in convs])
        ct = jnp.stack([p["tau"] for p in convs]).astype(jnp.int32)
        cf = jnp.stack([p["flip"] for p in convs]).astype(jnp.int32)
    else:                       # conv-less program: dummy slot, never read
        cw = jnp.zeros((1, f, 4, cww), jnp.uint32)
        ct = jnp.zeros((1, f), jnp.int32)
        cf = jnp.zeros((1, f), jnp.int32)
    fcs = packed["fc"]
    n_max = max(p["w_words"].shape[0] for p in fcs)
    kw_max = max(p["w_words"].shape[1] for p in fcs)
    fw = jnp.stack([
        jnp.pad(p["w_words"], ((0, n_max - p["w_words"].shape[0]),
                               (0, kw_max - p["w_words"].shape[1])))
        for p in fcs])
    return {"cw": cw, "ct": ct, "cf": cf, "fw": fw}


def ensure_image(artifact, program: isa.Program):
    """Admission helper: accept any artifact form, return the weight image."""
    if _is_image_artifact(artifact):
        return artifact
    return build_weight_image(ensure_packed(artifact), program)


# ---------------------------------------------------------------------------
# Compiled inference plan: the packed-domain pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _IOStage:
    bits: int
    channels: int


@dataclasses.dataclass(frozen=True)
class _ConvStage:
    c: int                 # true input channel count
    features: int
    pool: bool


@dataclasses.dataclass(frozen=True)
class _FCStage:
    in_features: int
    out_features: int
    final: bool
    pack_out: bool         # hidden layer stays packed (out % 32 == 0)


@dataclasses.dataclass(frozen=True)
class InferencePlan:
    """A program compiled to a static pipeline of fused packed stages.

    Built once per program by :func:`compile_plan`; all geometry (map
    sizes, channel counts, pool flags, FC fan-in) is resolved at build
    time so the jitted forward is a straight-line chain of Pallas calls
    with no Python-level reinterpretation of the instruction stream.
    """
    program: isa.Program
    stages: Tuple[Any, ...]
    mega: Tuple[Any, ...] = ()   # static stage spec for the megakernel

    def forward(self, packed, images: jax.Array,
                interpret: bool | None = None,
                conv_tiles: Optional[Tuple[int, int]] = None):
        """Packed deployment forward. Returns (logits int32->f32, labels).

        ``conv_tiles`` overrides the fused conv kernel's (bf, bb) tile
        sizes; default is the autotune cache's entry for this (program,
        backend, batch), falling back to the kernel defaults when cold.

        Each stage runs in a ``jax.named_scope`` of its name (``encode``,
        ``conv<i>``, ``fc<i>``), and each layer's kernel carries the same
        name, so a profile lists device time per layer.
        """
        if conv_tiles is None:
            conv_tiles = autotune.conv_tiles(self.program, images.shape[0])
        bf, bb = conv_tiles
        ci = fi = 0
        x = logits = None
        for st in self.stages:
            if isinstance(st, _IOStage):
                with jax.named_scope("encode"):
                    x = na.thermometer_encode_packed(images, st.bits,
                                                     st.channels)
            elif isinstance(st, _ConvStage):
                p = packed["conv"][ci]
                name = f"conv{ci}"
                with jax.named_scope(name):
                    x = kops.binary_conv2x2_block(
                        x, p["w_words"], p["tau"], p["flip"], st.c,
                        pool=st.pool, bf=bf, bb=bb, interpret=interpret,
                        name=name)
                ci += 1
            else:
                name = f"fc{fi}"
                with jax.named_scope(name):
                    x, logits = self._fc(st, packed["fc"][fi], x, logits,
                                         interpret, name)
                fi += 1
        logits = logits.astype(jnp.float32)
        return logits, jnp.argmax(logits, axis=-1)

    @staticmethod
    def _fc(st, p, x, logits, interpret, name):
        """One FC stage: (next packed input, logits once final)."""
        if x.ndim == 4:
            # packed (B, H, W, F//32) words flatten directly into packed
            # FC rows: F % 32 == 0 makes the word order the row-major
            # channel order.
            x = x.reshape(x.shape[0], -1)
        s = kops.xnor_matmul(x, p["w_words"], st.in_features,
                             pack_out=st.pack_out, interpret=interpret,
                             name=name)
        if st.final:
            return x, s
        if st.pack_out:
            return s, logits
        # odd-width hidden FC: threshold at 0, repack
        return binarize.pack_signs(
            binarize.hard_sign(s.astype(jnp.float32)), axis=-1), logits

    def forward_mega(self, image, images: jax.Array,
                     interpret: bool | None = None,
                     bb: Optional[int] = None, ft: Optional[int] = None):
        """Whole-network megakernel forward: one resident ``pallas_call``.

        ``image`` is the weight-image artifact (``fold_params(...,
        image=True)`` / :func:`ensure_image`) — the full SRAM contents,
        VMEM-resident; inter-layer feature maps live in VMEM scratch and
        frame tiles of ``bb`` double-buffer through the grid, so the only
        HBM traffic is frames in, logits out (the chip's "no off-chip
        bandwidth" execution model).  Conv layers compute in f-tiles of
        ``ft`` neurons (0 = all F per chunk — the VMEM-headroom knob for
        wide S modes).  ``bb``/``ft`` left as ``None`` resolve through
        the persistent autotune cache (``kernels.autotune``), falling
        back to the historical defaults when cold.  Tile sizes are a pure
        schedule choice: bit-exact vs :meth:`forward` for every setting.
        """
        bb, ft = autotune.mega_tiles(self.program, images.shape[0],
                                     bb=bb, ft=ft)
        logits = kops.megakernel_forward(image, images, spec=self.mega,
                                         bb=bb, ft=ft, interpret=interpret)
        logits = logits.astype(jnp.float32)
        return logits, jnp.argmax(logits, axis=-1)

    def make_fn(self, interpret: bool | None = None,
                megakernel: bool = False, bb: Optional[int] = None,
                ft: Optional[int] = None):
        """jit: (artifact, images) -> (logits, labels).

        ``megakernel=True`` runs the whole-network resident kernel and
        expects the weight-image artifact; default is the staged pipeline
        on the packed per-layer artifact.
        """
        @jax.jit
        def fn(artifact, images):
            if megakernel:
                return self.forward_mega(artifact, images,
                                         interpret=interpret, bb=bb, ft=ft)
            return self.forward(artifact, images, interpret=interpret)
        return fn

    def make_serve_fn(self, mesh=None, donate_frames: bool = False,
                      interpret: bool | None = None,
                      megakernel: bool = False, bb: Optional[int] = None,
                      ft: Optional[int] = None):
        """Serving entry point: jit'd (artifact, frames) -> (logits, labels).

        The deployment-side twin of :meth:`make_fn`, with two extra knobs
        the offline path doesn't need:

        * ``mesh`` — a 1-axis device mesh (see ``distributed.sharding.
          serve_mesh``).  The packed artifact is kept fully replicated
          (one weight replica per device — the chip's LD-once schedule,
          per device) and the frame batch is scattered on the batch axis
          with ``shard_map``; each device runs the whole packed pipeline
          on its frame shard.  The batch size must be divisible by the
          mesh's device count.  A 1-device mesh (or ``None``) degrades
          to a plain jit.
        * ``donate_frames`` — donate the streamed frame buffer to the
          computation; a continuous serving loop re-stages frames every
          dispatch and never reads a dispatched buffer again, so the
          runtime may reuse it in place (a no-op on backends without
          buffer donation).

        ``megakernel=True`` swaps the staged stage chain for the resident
        whole-network kernel (artifact = the weight image); the sharding
        story is unchanged — the image replicates like the packed
        artifact, frames scatter on batch.
        """
        if megakernel:
            fwd = lambda image, frames: self.forward_mega(
                image, frames, interpret=interpret, bb=bb, ft=ft)
        else:
            fwd = lambda packed, frames: self.forward(packed, frames,
                                                      interpret=interpret)
        if mesh is not None and mesh.devices.size > 1:
            from jax.sharding import PartitionSpec as P
            axis = mesh.axis_names[0]
            fwd = jax.shard_map(fwd, mesh=mesh,
                                in_specs=(P(), P(axis)),
                                out_specs=(P(axis), P(axis)),
                                check_vma=False)   # Pallas outputs
                                                   # carry no vma type
        donate = (1,) if donate_frames else ()
        return jax.jit(fwd, donate_argnums=donate)


@functools.lru_cache(maxsize=64)
def compile_plan(program: isa.Program) -> InferencePlan:
    """Resolve a program's geometry into a static packed-stage pipeline.

    Alongside the staged stage chain (one fused Pallas call per layer,
    kept as the fallback + oracle), the plan carries the megakernel's
    static stage spec — the same geometry lowered for the single
    resident ``pallas_call`` (``kernels.megakernel``).
    """
    stages = []
    mega = []
    for (ins, in_h, in_w, in_c, _oh, _ow, _oc) in isa.layer_geometry(program):
        if isinstance(ins, isa.IOInstr):
            stages.append(_IOStage(bits=ins.bits, channels=ins.channels))
            mega.append(("io", ins.height, ins.width, ins.in_channels,
                         ins.bits, ins.channels))
        elif isinstance(ins, isa.ConvInstr):
            if ins.features % binarize.PACK_WIDTH:
                raise isa.ProgramError(
                    f"packed plan needs conv F % {binarize.PACK_WIDTH} == 0, "
                    f"got {ins.features}")
            stages.append(_ConvStage(c=in_c, features=ins.features,
                                     pool=ins.maxpool))
            mega.append(("conv", in_h, in_w, in_c, ins.features,
                         ins.maxpool))
        else:
            pack_out = (not ins.final
                        and ins.out_features % binarize.PACK_WIDTH == 0)
            stages.append(_FCStage(in_features=ins.in_features,
                                   out_features=ins.out_features,
                                   final=ins.final, pack_out=pack_out))
            mega.append(("fc", ins.in_features, ins.out_features,
                         ins.final, pack_out))
    return InferencePlan(program=program, stages=tuple(stages),
                         mega=tuple(mega))


def compile_family(variants: Mapping[str, isa.Program]
                   ) -> Dict[str, InferencePlan]:
    """Compile a program *family*: one task at several operating points.

    Family members (e.g. cifar9 at S=1/S=2/S=4 and truncated depth, see
    ``networks.FAMILIES``) must be interchangeable per frame: identical
    IO geometry (height, width, raw channels, input precision) so any
    submitted frame can be served by any member, and an identical class
    count so their labels live in one space.  Validates both and returns
    ``{variant name: InferencePlan}`` — the serving layer's
    operating-point controller swaps among these per dispatch.
    """
    if not variants:
        raise ValueError("compile_family needs at least one variant")
    plans: Dict[str, InferencePlan] = {}
    ref_name = ref_io = ref_classes = None
    for name, prog in variants.items():
        isa.validate(prog)
        io = prog.instrs[0]
        geom = (io.height, io.width, io.in_channels, io.bits)
        classes = prog.instrs[-1].out_features
        if ref_io is None:
            ref_name, ref_io, ref_classes = name, geom, classes
        elif geom != ref_io:
            raise isa.ProgramError(
                f"family variants disagree on IO geometry: {ref_name} takes "
                f"(h, w, c, bits) = {ref_io}, {name} takes {geom} — one "
                "frame stream must be servable by every variant")
        elif classes != ref_classes:
            raise isa.ProgramError(
                f"family variants disagree on class count: {ref_name} has "
                f"{ref_classes}, {name} has {classes}")
        plans[name] = compile_plan(prog)
    return plans


# ---------------------------------------------------------------------------
# Composite plans: true sub-array sharing across resident programs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompositePlan:
    """Several programs compiled as ONE shared-array dispatch unit.

    The chip's S-mode recombination runs its sub-arrays *concurrently*:
    4xS4, 2xS2, 2xS4+1xS2, ... sub-arrays each execute their own program
    on their own frame stream in the same cycle.  A
    ``CompositePlan`` is the compiled form of that recombination: the
    members' weight images pack side-by-side on the F axis into one
    composite SRAM image (:func:`pack_programs`), each member's stages
    carry static F/N offsets into it, and :meth:`forward` runs every
    member's frames through ONE ``pallas_call`` per batch
    (``kernels.megakernel.composite_forward``) — bit-exact vs dispatching
    each member solo, but at full-array occupancy instead of 1/S.
    """
    names: Tuple[str, ...]
    programs: Tuple[isa.Program, ...]
    plans: Tuple[InferencePlan, ...]
    spec: Tuple[Any, ...]          # per-member stage specs with offsets

    @property
    def classes(self) -> Tuple[int, ...]:
        return tuple(sp[-1][2] for sp in self.spec)

    @property
    def n_groups(self) -> int:
        """Member-group count of the composite spec (per-group ``ft``
        tuples carry one entry per group)."""
        return len(kops.member_groups(self.spec))

    def forward(self, image, frames, interpret: bool | None = None,
                bb: Optional[int] = None, ft=None):
        """Shared dispatch: per-member frames -> per-member (logits, labels).

        ``frames`` is a mapping keyed by member name or a sequence in
        ``names`` order; member batches may be ragged (each is padded to
        the longest internally, padding trimmed on return).  Returns
        (logits, labels) as tuples in ``names`` order.  ``bb``/``ft``
        default through the autotune cache under the composite's own
        fingerprint; a per-group tuned entry resolves ``ft`` to a tuple
        with one f-tile per member group (pass an int or tuple
        explicitly to override).  Tile sizes are a pure schedule choice
        — bit-exact for every setting.
        """
        if isinstance(frames, Mapping):
            frames = tuple(frames[n] for n in self.names)
        else:
            frames = tuple(frames)
        batch = max(f.shape[0] for f in frames)
        bb, ft = autotune.composite_tiles(self.programs, batch, bb=bb, ft=ft,
                                          per_group=True,
                                          n_groups=self.n_groups)
        outs = kops.composite_forward(image, frames, spec=self.spec,
                                      bb=bb, ft=ft, interpret=interpret)
        logits = tuple(o.astype(jnp.float32) for o in outs)
        return logits, tuple(jnp.argmax(l, axis=-1) for l in logits)

    def make_serve_fn(self, mesh=None, donate_frames: bool = False,
                      interpret: bool | None = None,
                      bb: Optional[int] = None, ft: Optional[int] = None):
        """jit: (composite image, frames tuple) -> (logits, labels) tuples.

        Mirrors :meth:`InferencePlan.make_serve_fn`: with a ``mesh`` the
        composite image replicates per device and every member's frame
        batch scatters on its own batch axis; donation covers the whole
        frames tuple.
        """
        fwd = lambda image, frames: self.forward(image, frames,
                                                 interpret=interpret,
                                                 bb=bb, ft=ft)
        if mesh is not None and mesh.devices.size > 1:
            from jax.sharding import PartitionSpec as P
            axis = mesh.axis_names[0]
            fwd = jax.shard_map(fwd, mesh=mesh,
                                in_specs=(P(), P(axis)),
                                out_specs=(P(axis), P(axis)),
                                check_vma=False)   # Pallas outputs
                                                   # carry no vma type
        donate = (1,) if donate_frames else ()
        return jax.jit(fwd, donate_argnums=donate)


def pack_programs(programs: Mapping[str, isa.Program],
                  artifacts: Mapping[str, Any], *,
                  exact_tiling: bool = True):
    """Compile a shared-array composite: (CompositePlan, composite image).

    ``programs`` maps member names to validated ISA programs whose
    S-modes must tile the 256-channel array exactly (sum of 256/S == 256
    — 4xS4, 2xS2, 2xS4+1xS2, ...); ``artifacts`` maps the same names to
    any admissible artifact form (float-folded / packed / weight image).
    ``exact_tiling=False`` lifts the tiling constraint — the image
    layout generalizes to any total F — for packs whose members execute
    *sequentially* within one dispatch (the fused cascade: detector then
    recognizer, never both at once) rather than concurrently; concurrent
    composites keep the exact-tiling contract.

    The composite weight image packs the members side-by-side on the F
    axis — the TPU analogue of loading several programs into disjoint
    sub-array rows of the one weight SRAM:

      ``cw``: (Lc, 256, 4, Cw_max) uint32, member m's conv-layer-i words
          at rows [f_off_m, f_off_m + 256/S_m); rows past a member's
          depth (or a member's unused trailing channel words) stay zero
          and are never read — the kernel slices statically per member;
      ``ct``/``cf``: (Lc, 256) int32 thresholds / directions, same rows;
      ``fw``: (Lf, N_total, Kw_max) uint32 FC words, members side-by-side
          on the N axis per FC ordinal.
    """
    names = tuple(programs)
    if not names:
        raise ValueError("pack_programs needs at least one program")
    progs = tuple(programs[n] for n in names)
    for p in progs:
        isa.validate(p)
    widths = [isa.ARRAY_CHANNELS // p.s for p in progs]
    if exact_tiling and len(progs) > 1 and sum(widths) != isa.ARRAY_CHANNELS:
        raise isa.ProgramError(
            f"S-modes {[p.s for p in progs]} do not tile the array "
            f"exactly: sum(256/S) = {sum(widths)} != {isa.ARRAY_CHANNELS}")
    plans = tuple(compile_plan(p) for p in progs)
    images = [ensure_image(artifacts[n], p) for n, p in zip(names, progs)]

    f_offs, off = [], 0
    for w in widths:
        f_offs.append(off)
        off += w
    ftot = off

    lc = max(img["cw"].shape[0] for img in images)
    kwc = max(img["cw"].shape[3] for img in images)
    cw = jnp.zeros((lc, ftot, 4, kwc), jnp.uint32)
    ct = jnp.zeros((lc, ftot), jnp.int32)
    cf = jnp.zeros((lc, ftot), jnp.int32)
    for img, fo in zip(images, f_offs):
        ncm, fm, _, kwm = img["cw"].shape
        cw = cw.at[:ncm, fo:fo + fm, :, :kwm].set(img["cw"])
        ct = ct.at[:ncm, fo:fo + fm].set(img["ct"])
        cf = cf.at[:ncm, fo:fo + fm].set(img["cf"])

    # FC rows: true (N, Kw) per member per FC ordinal, packed side-by-side
    fc_geoms = [[(st[2], -(-st[1] // binarize.PACK_WIDTH))
                 for st in plan.mega if st[0] == "fc"] for plan in plans]
    lf = max(len(g) for g in fc_geoms)
    n_offs, row = [], [0] * lf
    for g in fc_geoms:
        offs = []
        for li, (n, _kw) in enumerate(g):
            offs.append(row[li])
            row[li] += n
        n_offs.append(tuple(offs))
    n_tot = max(row)
    kw_tot = max(kw for g in fc_geoms for _n, kw in g)
    fw = jnp.zeros((lf, n_tot, kw_tot), jnp.uint32)
    for img, g, offs in zip(images, fc_geoms, n_offs):
        for li, ((n, kw), o) in enumerate(zip(g, offs)):
            fw = fw.at[li, o:o + n, :kw].set(img["fw"][li, :n, :kw])

    mspecs = []
    for plan, fo, offs in zip(plans, f_offs, n_offs):
        fi, st_out = 0, []
        for st in plan.mega:
            if st[0] == "io":
                st_out.append(st)
            elif st[0] == "conv":
                st_out.append(st + (fo,))
            else:
                st_out.append(st + (offs[fi],))
                fi += 1
        mspecs.append(tuple(st_out))

    cplan = CompositePlan(names=names, programs=progs, plans=plans,
                          spec=tuple(mspecs))
    return cplan, {"cw": cw, "ct": ct, "cf": cf, "fw": fw}


# ---------------------------------------------------------------------------
# Cascade plans: in-kernel detector -> recognizer escalation
# ---------------------------------------------------------------------------

_INT32_MIN = -(2 ** 31)
_INT32_MAX = 2 ** 31 - 1


@dataclasses.dataclass(frozen=True)
class CascadePlan:
    """A detector + recognizer pair compiled as ONE fused dispatch unit.

    The paper's always-on hierarchy with the control flow *inside* the
    kernel: both stages' weight images share one composite SRAM image
    (:func:`pack_cascade`), the detector runs over every frame tile, the
    escalation decision (positive-class logit margin >= threshold) is
    made in-kernel, and the recognizer drains only the escalated lanes
    through bounded-iteration control flow
    (``kernels.megakernel.cascade_forward``) — one dispatch, no host
    round-trip between the stages.  Unlike a :class:`CompositePlan` the
    two members run *sequentially* on the array (detector phase, then
    recognizer phase), so their S-modes need not tile the 256 channels.

    The escalation rule is bit-exact vs the host cascade's float rule:
    integer logits satisfy ``m >= margin  <=>  m >= ceil(margin)``, and
    :meth:`margin_ctrl` folds the host float margin into the int32
    threshold the kernel compares against (``+/-inf`` map to sentinels
    beyond any reachable margin — FC logit magnitudes are bounded by the
    fan-in, orders below 2^31).
    """
    detector: str
    recognizer: str
    programs: Tuple[isa.Program, ...]          # (det, rec)
    plans: Tuple[InferencePlan, ...]
    spec: Tuple[Any, ...]                      # 2-member composite spec
    positive_class: int = 1

    @property
    def classes(self) -> Tuple[int, int]:
        return tuple(sp[-1][2] for sp in self.spec)

    @property
    def n_groups(self) -> int:
        return len(kops.member_groups(self.spec))

    @staticmethod
    def margin_ctrl(margin: float, n_real: int):
        """Fold a host-side float escalation margin into the kernel's
        dynamic ``(1, 2)`` int32 control word ``[threshold, n_real]``.

        For integer margins m, ``m >= margin`` (the host rule, float)
        holds iff ``m >= ceil(margin)`` — so the ceil makes the integer
        compare bit-exact for *every* float margin.  ``-inf`` (escalate
        all) and ``+inf`` (escalate none) clamp to the int32 extremes,
        both unreachable by real margins.  ``n_real`` masks padding
        lanes out of escalation.
        """
        if math.isnan(margin):
            raise ValueError("escalation margin must not be NaN")
        thr = (_INT32_MIN if margin == float("-inf") else
               _INT32_MAX if margin == float("inf") else
               int(min(max(math.ceil(margin), _INT32_MIN), _INT32_MAX)))
        return jnp.array([[thr, int(n_real)]], jnp.int32)

    def forward_fused(self, image, frames: jax.Array, ctrl,
                      interpret: bool | None = None,
                      bb: Optional[int] = None, ft=None,
                      rb: Optional[int] = None, check_every: int = 1):
        """One fused dispatch: frames -> both stages' answers.

        ``ctrl`` is the dynamic control word from :meth:`margin_ctrl`
        (dynamic so margin sweeps and ragged batches never retrace).
        Returns ``(det_logits, det_labels, rec_logits, rec_labels,
        queue, counts)`` — logits float32, labels int; ``counts[0] = E``
        escalated frames, ``queue[:E]`` their ascending frame indices,
        ``rec_*[k]`` answering frame ``queue[k]`` (compacted);
        ``counts[1]`` the recognizer frame slots computed (>= E — the
        drain chunks' padding, billed by the serving layer).  ``bb``/
        ``ft`` resolve through the autotune cache under the pair's
        composite fingerprint; tile sizes and ``rb``/``check_every``
        are pure schedule choices — bit-exact for every setting.
        """
        batch = frames.shape[0]
        bb, ft = autotune.composite_tiles(self.programs, batch, bb=bb, ft=ft,
                                          per_group=True,
                                          n_groups=self.n_groups)
        det, rec, queue, counts = kops.cascade_forward(
            image, frames, ctrl, spec=self.spec, bb=bb,
            rb=0 if rb is None else rb, ft=ft, check_every=check_every,
            positive_class=self.positive_class, interpret=interpret)
        det_l = det.astype(jnp.float32)
        rec_l = rec.astype(jnp.float32)
        return (det_l, jnp.argmax(det_l, axis=-1),
                rec_l, jnp.argmax(rec_l, axis=-1), queue, counts)

    def make_serve_fn(self, mesh=None, donate_frames: bool = False,
                      interpret: bool | None = None,
                      bb: Optional[int] = None, ft: Optional[int] = None):
        """jit: (image, frames, ctrl) -> fused cascade outputs.

        The fused cascade does not shard: the in-kernel escalation queue
        compacts across the whole batch, so scattering frames over a
        mesh would split the queue mid-dispatch.  A 1-device mesh (or
        ``None``) serves on the default device; multi-device meshes are
        rejected — serve the cascade host-side (``CascadePipeline``
        without ``fused``) to shard the stages independently.
        """
        if mesh is not None and mesh.devices.size > 1:
            raise ValueError(
                "fused cascade dispatch does not shard over a multi-device "
                "mesh (the escalation queue is batch-global); use the "
                "host-side cascade for sharded stages")
        fwd = lambda image, frames, ctrl: self.forward_fused(
            image, frames, ctrl, interpret=interpret, bb=bb, ft=ft)
        donate = (1,) if donate_frames else ()
        return jax.jit(fwd, donate_argnums=donate)


def pack_cascade(programs: Mapping[str, isa.Program],
                 artifacts: Mapping[str, Any], *,
                 detector: str, recognizer: str,
                 positive_class: int = 1):
    """Compile a fused cascade pair: (CascadePlan, composite image).

    ``programs``/``artifacts`` are keyed like :func:`pack_programs`;
    ``detector``/``recognizer`` name the two members.  The stages must
    agree on frame geometry (one stream feeds both) and the detector
    must have >= 2 classes with ``positive_class`` among them.  The
    composite image is the ordinary side-by-side F-axis pack with the
    detector at offset 0 — built with ``exact_tiling=False`` because the
    stages run sequentially within the dispatch (see
    :func:`pack_programs`).
    """
    if detector == recognizer:
        raise isa.ProgramError(
            "cascade stages must be distinct programs, got "
            f"{detector!r} twice")
    for name in (detector, recognizer):
        if name not in programs:
            raise KeyError(f"cascade stage {name!r} missing from programs "
                           f"(have {sorted(programs)})")
    det_prog, rec_prog = programs[detector], programs[recognizer]
    iod, ior = det_prog.instrs[0], rec_prog.instrs[0]
    gd = (iod.height, iod.width, iod.in_channels, iod.bits)
    gr = (ior.height, ior.width, ior.in_channels, ior.bits)
    if gd != gr:
        raise isa.ProgramError(
            f"cascade stages disagree on frame geometry: detector takes "
            f"(h, w, c, bits) = {gd}, recognizer takes {gr} — one frame "
            "stream must feed both stages")
    ncd = det_prog.instrs[-1].out_features
    if ncd < 2:
        raise isa.ProgramError(
            f"detector needs >= 2 classes for a logit margin, got {ncd}")
    if not 0 <= positive_class < ncd:
        raise isa.ProgramError(
            f"positive_class {positive_class} out of range for the "
            f"detector's {ncd} classes")
    cplan, image = pack_programs(
        {detector: det_prog, recognizer: rec_prog},
        {detector: artifacts[detector], recognizer: artifacts[recognizer]},
        exact_tiling=False)
    plan = CascadePlan(detector=detector, recognizer=recognizer,
                       programs=cplan.programs, plans=cplan.plans,
                       spec=cplan.spec, positive_class=positive_class)
    return plan, image


# ---------------------------------------------------------------------------
# Delta plans: in-kernel frame-delta gating for always-on video streams
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeltaPlan:
    """One program compiled for delta-gated always-on serving.

    The always-on workload BinarEye's headline numbers assume is *video*:
    consecutive frames of a quiet scene are nearly identical, so running
    the full network on every frame burns energy re-deriving the label it
    already has.  This plan pairs the program's whole-network megakernel
    with resident temporal state — each stream's last packed thermometer
    frame and its cached logits — and gates recompute *inside* the
    dispatch (``kernels.megakernel.delta_forward``): the packed Hamming
    distance ``popcount(cur XOR last)`` is compared per lane against a
    dynamic int32 threshold, changed lanes compact into the cascade's
    escalation-queue idiom and recompute, skipped lanes emit their cached
    logits at delta-compute-only cost.

    The gate is bit-exact vs a host reference: packed Hamming distances
    are integers, so ``d >= threshold  <=>  d >= ceil(threshold)``, and
    :meth:`delta_ctrl` folds host float thresholds into the kernel's
    int32 control word (``-inf`` recomputes everything — the forced
    first-dispatch / post-reset state — and ``+inf`` skips everything;
    both sentinels are beyond any reachable distance).  At threshold 0
    every live lane recomputes and the merged logits equal the plain
    megakernel's bit for bit.
    """
    name: str
    program: isa.Program
    plan: InferencePlan
    spec: Tuple[Any, ...]                      # 1-member composite spec

    @property
    def classes(self) -> int:
        return self.spec[0][-1][2]

    @property
    def geometry(self) -> Tuple[int, int, int]:
        io = self.spec[0][0]
        return io[1], io[2], io[3]

    @property
    def packed_words(self) -> Tuple[int, int, int]:
        """(H, W, channels//32): one stream's last-frame state shape."""
        io = self.spec[0][0]
        return io[1], io[2], io[5] // binarize.PACK_WIDTH

    @staticmethod
    def delta_ctrl(threshold: float, n_real: int):
        """Fold a host-side float change threshold into the kernel's
        dynamic ``(1, 2)`` int32 control word ``[threshold, n_real]``.

        Packed Hamming distances d are integers, so ``d >= threshold``
        (the host rule, float) holds iff ``d >= ceil(threshold)`` — the
        ceil makes the integer compare bit-exact for every float
        threshold.  ``-inf`` (recompute all — the cold-state dispatch)
        and ``+inf`` (skip all) clamp to the int32 extremes, both
        unreachable by real distances.  ``n_real`` masks padding lanes
        out of the change queue.
        """
        if math.isnan(threshold):
            raise ValueError("delta threshold must not be NaN")
        thr = (_INT32_MIN if threshold == float("-inf") else
               _INT32_MAX if threshold == float("inf") else
               int(min(max(math.ceil(threshold), _INT32_MIN), _INT32_MAX)))
        return jnp.array([[thr, int(n_real)]], jnp.int32)

    def init_state(self, n: int):
        """Cold per-stream state for ``n`` streams: zeroed last-frame
        words + zeroed cached logits.  Cold state is *not* a valid gate
        reference — pair the first dispatch with a ``-inf`` threshold
        (``delta_ctrl(float("-inf"), n)``) so every lane recomputes and
        the state warms from real frames."""
        h, w, cw = self.packed_words
        return (jnp.zeros((n, h, w, cw), jnp.uint32),
                jnp.zeros((n, self.classes), jnp.int32))

    def forward_delta(self, image, frames: jax.Array, last, llog, ctrl,
                      interpret: bool | None = None,
                      bb: Optional[int] = None, ft: Optional[int] = None,
                      rb: Optional[int] = None, check_every: int = 1):
        """One gated dispatch: advance every stream by one time step.

        ``ctrl`` is the dynamic control word from :meth:`delta_ctrl`
        (dynamic, so threshold sweeps and ragged batches never retrace).
        Returns ``(logits, labels, new_last, new_llog, queue, counts,
        deltas)``: ``logits`` (float32) / ``labels`` merge fresh answers
        for changed lanes with cached answers for skipped lanes;
        ``new_last`` / ``new_llog`` are the next dispatch's state;
        ``counts[0] = K`` changed lanes, ``queue[:K]`` their ascending
        indices, ``counts[1]`` the frame slots computed (>= K — drain-
        chunk padding, billed by the serving layer); ``deltas`` the
        per-lane packed Hamming distances.  ``bb``/``ft`` resolve
        through the autotune cache; tile sizes and ``rb``/
        ``check_every`` are pure schedule choices — bit-exact for every
        setting.
        """
        bb, ft = autotune.mega_tiles(self.program, frames.shape[0],
                                     bb=bb, ft=ft)
        logits, new_last, queue, counts, deltas = kops.delta_forward(
            image, frames, last, llog, ctrl, spec=self.spec, bb=bb,
            rb=0 if rb is None else rb, ft=ft, check_every=check_every,
            interpret=interpret)
        lf = logits.astype(jnp.float32)
        return (lf, jnp.argmax(lf, axis=-1), new_last, logits,
                queue, counts, deltas)

    def make_serve_fn(self, mesh=None, donate_frames: bool = False,
                      interpret: bool | None = None,
                      bb: Optional[int] = None, ft: Optional[int] = None,
                      rb: Optional[int] = None, check_every: int = 1):
        """jit: (image, frames, last, llog, ctrl) -> gated outputs.

        The gated dispatch does not shard: the change queue compacts
        across the whole batch and the last-frame/last-logits state is
        batch-global resident VMEM, so scattering frames over a mesh
        would split both mid-dispatch.  A 1-device mesh (or ``None``)
        serves on the default device; multi-device meshes are rejected —
        shard by running one :class:`DeltaPlan` per device over disjoint
        stream sets instead.
        """
        if mesh is not None and mesh.devices.size > 1:
            raise ValueError(
                "delta-gated dispatch does not shard over a multi-device "
                "mesh (the change queue and resident last-frame state are "
                "batch-global); run one DeltaPlan per device over "
                "disjoint stream sets instead")
        fwd = lambda image, frames, last, llog, ctrl: self.forward_delta(
            image, frames, last, llog, ctrl, interpret=interpret,
            bb=bb, ft=ft, rb=rb, check_every=check_every)
        donate = (1, 2, 3) if donate_frames else ()
        return jax.jit(fwd, donate_argnums=donate)


def pack_delta(program: isa.Program, artifact, *, name: str = "program"):
    """Compile a delta-gated serving unit: (DeltaPlan, weight image).

    The image is the program's own megakernel weight image
    (:func:`ensure_image`) and the spec is the one-member composite lift
    of ``InferencePlan.mega`` — the gated kernel shares the megakernel's
    member body, so the recompute path is bit-exact vs ``forward_mega``
    by construction.
    """
    isa.validate(program)
    io = program.instrs[0]
    if io.channels % binarize.PACK_WIDTH:
        raise isa.ProgramError(
            f"delta gating needs IO channels % {binarize.PACK_WIDTH} == 0 "
            f"(packed Hamming distance), got {io.channels}")
    plan = compile_plan(program)
    spec = (tuple(st if st[0] == "io" else st + (0,)
                  for st in plan.mega),)
    image = ensure_image(artifact, program)
    return (DeltaPlan(name=name, program=program, plan=plan, spec=spec),
            image)


def forward_infer(folded, program: isa.Program, images: jax.Array,
                  use_kernels: bool = False, interpret: bool | None = None):
    """Deployment forward. Returns (logits, labels).

    ``use_kernels=True`` routes through the compiled packed plan (packing
    the float artifact on the fly if needed); ``use_kernels=False`` is
    the float +/-1 reference path the plan is tested bit-exact against.
    """
    if use_kernels:
        return compile_plan(program).forward(ensure_packed(folded), images,
                                             interpret=interpret)

    ci = fi = 0
    x = None
    for ins in program.instrs:
        if isinstance(ins, isa.IOInstr):
            x = na.thermometer_encode(images, ins.bits, ins.channels)
        elif isinstance(ins, isa.ConvInstr):
            p = folded["conv"][ci]
            s = na.conv2x2(x, p["w"])
            x = na.comparator(s, p["tau"], p["flip"])
            if ins.maxpool:
                x = na.maxpool2x2(x)
            ci += 1
        elif isinstance(ins, isa.FCInstr):
            if x.ndim == 4:
                x = x.reshape(x.shape[0], -1)
            p = folded["fc"][fi]
            s = na.fc(x, p["w"])
            x = s if ins.final else binarize.hard_sign(s)
            fi += 1
    return x, jnp.argmax(x, axis=-1)


def make_infer_fn(program: isa.Program, use_kernels: bool = False):
    """Bind the program (static) and jit: images, folded -> labels."""
    @functools.partial(jax.jit, static_argnames=())
    def fn(folded, images):
        return forward_infer(folded, program, images, use_kernels=use_kernels)
    return fn
