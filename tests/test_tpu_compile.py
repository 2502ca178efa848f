"""The main-path kernels compile for a TPU v5e at real cifar9 shapes.

Interpret mode (every other kernel test) checks results but not what the
TPU compiler accepts: unaligned block shapes, unsigned reductions and
VMEM overflows only show when Mosaic lowers the kernel.  These tests
compile each kernel for a *described* v5e chip (no chip attached) and
check that the compiled program holds the Mosaic kernel.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, and every
test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.chip import interpreter, networks
from repro.kernels import binary_conv2x2_block as bcb
from repro.kernels import megakernel as mk
from repro.kernels import xnor_matmul as xm

BATCH = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    executable for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(program):
    params = interpreter.init_params(jax.random.PRNGKey(0), program)
    return interpreter.fold_params(params, program, packed=True)


def _shapes(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


# cifar9 at S=1: the first conv (32x32 map, C=F=256 -> 8 words) and the
# first pooled layer (29x29 -> 14x14); S=4 at its first conv
@pytest.mark.parametrize("h,f,pool", [(32, 256, False), (29, 256, True),
                                      (32, 64, False)])
def test_staged_conv_block_compiles(one_chip, h, f, pool):
    kw = f // 32
    _compile(lambda a, w, t, fl: bcb.binary_conv2x2_block(
                 a, w, t, fl, c=f, pool=pool, interpret=False),
             _spec(one_chip, (BATCH, h, h, kw), jnp.uint32),
             _spec(one_chip, (f, 4, kw), jnp.uint32),
             _spec(one_chip, (f,), jnp.int32),
             _spec(one_chip, (f,), jnp.int32))


@pytest.mark.parametrize("pack_out,n", [(False, 10), (True, 256)])
def test_xnor_matmul_compiles(one_chip, pack_out, n):
    # the cifar9 S=1 classifier FC: K = 2*2*256 = 1024 = 32 words
    _compile(lambda a, w: xm.xnor_matmul(a, w, k=1024, pack_out=pack_out,
                                         interpret=False),
             _spec(one_chip, (BATCH, 32), jnp.uint32),
             _spec(one_chip, (n, 32), jnp.uint32))


@pytest.mark.parametrize("name", ["cifar9_s1", "cifar9_s4"])
def test_megakernel_compiles(one_chip, name):
    program = networks.REGISTRY[name]()
    plan = interpreter.compile_plan(program)
    image = interpreter.build_weight_image(_packed(program), program)
    _compile(lambda img, fr: mk.megakernel_forward(
                 img, fr, spec=plan.mega, interpret=False),
             _shapes(one_chip, image),
             _spec(one_chip, (BATCH, 32, 32, 3), jnp.int32))


def test_composite_compiles(one_chip):
    progs = {f"s4_{i}": networks.cifar9(4) for i in range(4)}
    cplan, image = interpreter.pack_programs(
        progs, {n: _packed(p) for n, p in progs.items()})
    _compile(lambda img, fr: mk.composite_forward(
                 img, fr, spec=cplan.spec, interpret=False),
             _shapes(one_chip, image),
             tuple(_spec(one_chip, (BATCH, 32, 32, 3), jnp.int32)
                   for _ in progs))


def test_cascade_compiles(one_chip):
    progs = {"face_detector": networks.face_detector(),
             "owner_detector": networks.owner_detector()}
    plan, image = interpreter.pack_cascade(
        progs, {n: _packed(p) for n, p in progs.items()},
        detector="face_detector", recognizer="owner_detector")
    _compile(lambda img, fr, ctrl: mk.cascade_forward(
                 img, fr, ctrl, spec=plan.spec, interpret=False),
             _shapes(one_chip, image),
             _spec(one_chip, (BATCH, 32, 32, 3), jnp.int32),
             _spec(one_chip, (1, 2), jnp.int32))


def test_delta_compiles(one_chip):
    program = networks.cifar9(4)
    plan, image = interpreter.pack_delta(program, _packed(program))
    h, w, cw = plan.packed_words
    _compile(lambda img, fr, last, llog, ctrl: mk.delta_forward(
                 img, fr, last, llog, ctrl, spec=plan.spec, interpret=False),
             _shapes(one_chip, image),
             _spec(one_chip, (BATCH, 32, 32, 3), jnp.int32),
             _spec(one_chip, (BATCH, h, w, cw), jnp.uint32),
             _spec(one_chip, (BATCH, plan.classes), jnp.int32),
             _spec(one_chip, (1, 2), jnp.int32))


@pytest.mark.parametrize("name", ["cifar9_s1", "cifar9_s4"])
def test_staged_plan_names_each_layer_kernel(one_chip, name):
    """The staged plan names each layer's Mosaic kernel after its stage
    (``conv<i>``, ``fc<i>``), so a profile lists device time per layer."""
    program = networks.REGISTRY[name]()
    plan = interpreter.compile_plan(program)
    packed = _packed(program)
    text = jax.jit(lambda p, fr: plan.forward(p, fr, interpret=False)).lower(
        _shapes(one_chip, packed),
        _spec(one_chip, (BATCH, 32, 32, 3), jnp.int32)).compile().as_text()
    kernels = [ln.split("=", 1)[0].strip().lstrip("%")
               for ln in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    n_conv = len(packed["conv"])
    names = {k.rsplit(".", 1)[0] for k in kernels}
    assert names == {f"conv{i}" for i in range(n_conv)} | {"fc0"}, kernels
    assert "%binary_conv2x2_block" not in text
