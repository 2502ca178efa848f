"""The benchmark's machinery: files found by name, seeded inputs, the
traffic loop, the window and its trace, and the check of every answer.

Everything that belongs to one configuration, cell, driver or metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the programs' layer lists, the source, and
  the plain reference (``configs/<reference>.py``) that checks them;
* ``workloads/<cell>.json``: the configuration, the driver module and its
  deployment settings, and the traffic block;
* ``drivers/<driver>.py``: builds the system under test through its
  serving entry point and exposes submit/step/flush to the loops here;
* ``metrics/<metric>.py``: ``read(rec)`` returns the metric from the run
  record, or ``None`` where the run has nothing to read.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

REF_BLOCK = 256            # frames per reference block
TRACE_FROM = 0.75          # a traced run traces the window from here on


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_file(name: str) -> dict:
    return load_json(os.path.join(HERE, "workloads", name + ".json"))


def config_file(name: str) -> dict:
    return load_json(os.path.join(HERE, "configs", name + ".json"))


def reference_module(config: dict):
    return load_module(os.path.join(HERE, "configs",
                                    config["reference"] + ".py"),
                       "bench_ref_" + config["reference"])


def driver_module(name: str):
    return load_module(os.path.join(HERE, "drivers", name + ".py"),
                       "bench_driver_" + name)


def metric_reader(name: str):
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


_COMPILES = [0]


def _count_compile(event: str, *_args, **_kw) -> None:
    if event in ("/jax/core/compile/backend_compile_duration",
                 "/jax/compilation_cache/cache_retrieval_time_sec"):
        _COMPILES[0] += 1


def compiles() -> int:
    """Programs compiled or loaded from the compile cache so far in this
    process (counted from JAX's monitoring events)."""
    return _COMPILES[0]


def watch_compiles() -> None:
    import jax
    if not getattr(watch_compiles, "done", False):
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        watch_compiles.done = True


def prng_key(seed: int):
    """A JAX key for any whole-number seed (more than 32 bits allowed)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def layers_of(prog, isa) -> List[dict]:
    """An ISA program as a configuration's layer list."""
    got = []
    for ins in prog.instrs:
        if isinstance(ins, isa.IOInstr):
            got.append({"op": "io", "height": ins.height, "width": ins.width,
                        "in_channels": ins.in_channels, "bits": ins.bits,
                        "channels": ins.channels})
        elif isinstance(ins, isa.ConvInstr):
            got.append({"op": "conv", "height": ins.height,
                        "width": ins.width, "features": ins.features,
                        "maxpool": ins.maxpool})
        else:
            got.append({"op": "fc", "in_features": ins.in_features,
                        "out_features": ins.out_features,
                        "final": ins.final})
    return got


class Harness:
    """One run of one cell: its inputs, its spans and its log."""

    def __init__(self, cell: dict, config: dict, *, seed: int,
                 seconds: float, trace: bool, devices,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 interpret: Optional[bool] = None):
        self.cell, self.config = cell, config
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = list(devices)
        self.clock, self.sleep = clock, sleep
        self.interpret = interpret
        self.ref = reference_module(config)
        self.programs = {}                   # name -> isa.Program
        self.layers = {n: p["layers"] for n, p in config["programs"].items()}
        self.weights = {}                    # name -> reference weights
        self.artifacts = {}                  # name -> packed artifact
        self.bank = None                     # (N, H, W, C) int32, host
        self.order = None                    # seeded bank cycle
        self._trace_dir = None
        self._tracing = False
        self.window = None                   # (t0, t1) on self.clock
        self.traced = None                   # [call, started, stopped]

    # -- inputs ---------------------------------------------------------------

    def build(self) -> None:
        """The programs of the system under test, their weights (one
        jitted call on the device, from the seed) and the frame bank."""
        import jax
        from repro.core.chip import interpreter, isa, networks
        for name, spec in self.config["programs"].items():
            prog = networks.REGISTRY[spec["registry"]]()
            self._check_layers(name, prog, spec, isa)
            self.programs[name] = prog
        names = tuple(self.programs)
        ref, layers = self.ref, self.layers

        @jax.jit
        def build(key):
            out = {}
            for i, name in enumerate(names):
                k = jax.random.fold_in(key, i)
                io = layers[name][0]
                calib = jax.random.randint(
                    jax.random.fold_in(k, 1 << 20),
                    (8, io["height"], io["width"], io["in_channels"]),
                    0, 2 ** io["bits"])
                w = ref.init(layers[name], k, calib)
                folded = {"conv": [dict(w=p["w"], tau=p["tau"],
                                        flip=p["flip"]) for p in w["conv"]],
                          "fc": [dict(w=p["w"]) for p in w["fc"]]}
                out[name] = (w, interpreter.pack_folded(folded))
            return out

        with jax.default_device(self.devices[0]):
            built = jax.block_until_ready(build(prng_key(self.seed)))
        for name in names:
            self.weights[name], self.artifacts[name] = built[name]
        io = next(iter(self.layers.values()))[0]
        n = int(self.cell["traffic"]["bank_frames"])
        rng = np.random.default_rng([self.seed, 1])
        self.bank = rng.integers(
            0, 2 ** io["bits"],
            (n, io["height"], io["width"], io["in_channels"]), dtype=np.int32)
        self.order = rng.permutation(n)

    @staticmethod
    def _check_layers(name, prog, spec, isa) -> None:
        """The registry's program must be the configuration's layer list."""
        if layers_of(prog, isa) != spec["layers"] or prog.s != spec["s"]:
            raise ValueError(f"program {name!r} ({spec['registry']}) is not "
                             "the configuration's layer list")

    def frame(self, k: int):
        """The k-th frame of the seeded cycle: (bank index, frame)."""
        idx = int(self.order[k % len(self.order)])
        return idx, self.bank[idx]

    # -- spans and the traced window -----------------------------------------

    def span(self, name: str):
        """A host span in the profiler's trace (while it records)."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def start_window(self) -> float:
        watch_compiles()
        self._compiles0 = compiles()
        t0 = self.clock()
        self.window = (t0, t0 + self.seconds)
        self._trace_at = (t0 + TRACE_FROM * self.seconds if self.trace
                          else float("inf"))
        return t0

    def poll_trace(self, now: float) -> bool:
        """Start the profiler once a traced run's window reaches its traced
        slice, the window's last part: the profiler slows the host, so
        the part before it serves at the untraced rate and the slice's
        own rate shows what tracing costs.  True when it started now."""
        if now < self._trace_at:
            return False
        import jax
        self._trace_at = float("inf")
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # harness spans and runtime
        opts.host_tracer_level = 1        # events only: a small trace
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._tracing = True
        self._window_span = self.span("window")
        self._window_span.__enter__()
        self.traced = [now, self.clock(), None]
        return True

    def stop_window(self) -> Optional[str]:
        """Stop the trace; returns the .xplane.pb path (traced runs whose
        window reached its traced slice)."""
        if not self._tracing:
            return None
        import jax
        self.traced[2] = self.clock()
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False
        found = glob.glob(os.path.join(self._trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        return found[0] if found else None

    def drop_trace(self) -> None:
        if self._trace_dir:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


class Log:
    """What the loop saw: every answer with the time it reached the
    harness, and the bank index of every submitted frame."""

    def __init__(self):
        self.index: Dict[int, int] = {}       # key -> bank index
        self.answers: List[Any] = []          # driver's result objects
        self.t_answer: List[float] = []
        self.counters: Dict[str, Any] = {}    # as the window left them
        self.counters_traced: Dict[str, Any] = {}  # as the trace found them
        self.queued_at_close = 0              # frames queued at the close
        self.trace_file: Optional[str] = None  # .xplane.pb (traced runs)

    def add(self, got, t: float) -> None:
        self.answers.extend(got)
        self.t_answer.extend([t] * len(got))


def closed_loop(h: Harness, target, traffic: dict) -> Log:
    """Callers that keep a backlog: before every step the queue is topped
    up to ``queued_batches`` full batches (of every live replica)."""
    log = Log()
    k = 0
    t0 = h.start_window()
    t_end = t0 + h.seconds
    target.begin_window(t0)
    while True:
        now = h.clock()
        if now >= t_end:
            break
        if h.poll_trace(now):
            log.counters_traced = target.counters()
        with h.span("submit"):
            need = int(traffic["queued_batches"]) * target.capacity() \
                - target.pending()
            for _ in range(max(0, need)):
                idx, fr = h.frame(k)
                log.index[target.submit(fr)] = idx
                k += 1
        with h.span("step"):
            got = target.step()
        log.add(got, h.clock())
    return _close(h, target, log)


LOOPS = {"closed": closed_loop}


def _close(h: Harness, target, log: Log) -> Log:
    """Close the window: counters as the window left them, the trace,
    then every answer still owed (not counted in the window's rate)."""
    log.counters = target.counters()
    log.counters["window_compiles"] = compiles() - h._compiles0
    log.queued_at_close = target.pending()
    log.trace_file = h.stop_window()
    with h.span("flush"):
        got = target.flush()
    log.add(got, h.clock())
    return log


# ---------------------------------------------------------------------------
# the check of every answer
# ---------------------------------------------------------------------------

def reference_outputs(h: Harness, name: str, indices: np.ndarray,
                      acc: str = "float32", block: int = REF_BLOCK):
    """The plain reference over bank frames ``indices``, in blocks of
    ``block`` frames, on the first device: (logits (n, classes), labels
    (n,))."""
    import jax
    import jax.numpy as jnp
    layers, w = h.layers[name], h.weights[name]
    fwd = jax.jit(lambda w, x: h.ref.forward(w, layers, x, acc))
    logits, labels = [], []
    with jax.default_device(h.devices[0]):
        for s in range(0, len(indices), block):
            blk = indices[s:s + block]
            pad = block - len(blk)
            x = h.bank[np.concatenate([blk, np.repeat(blk[-1:], pad)])]
            lg, lb = fwd(w, jnp.asarray(x))
            logits.append(np.asarray(lg)[:len(blk)])
            labels.append(np.asarray(lb)[:len(blk)])
    return np.concatenate(logits), np.concatenate(labels)


def check(h: Harness, target, log: Log) -> Dict[str, Dict[str, float]]:
    """Every answer against the plain reference: each number compared,
    with its limit.  ``missing``: frames submitted and never answered;
    ``duplicate``: frames answered more than once; the rest come from
    the driver module's comparison of the answers with the reference."""
    cols = target.columns(log.answers)
    keys = cols["key"]
    uniq, counts = np.unique(keys, return_counts=True)
    nums = {
        "missing": (len(set(log.index) - set(uniq.tolist())), 0),
        "duplicate": (int((counts > 1).sum()), 0),
    }
    known = np.array([k in log.index for k in keys.tolist()], dtype=bool)
    nums["unknown"] = (int((~known).sum()), 0)
    idx = np.array([log.index[k] for k in keys[known].tolist()],
                   dtype=np.int64)
    sel = {c: v[known] for c, v in cols.items()}
    nums.update(target.compare(h, sel, idx))
    return {k: {"value": float(v), "limit": float(lim)}
            for k, (v, lim) in nums.items()}


def classifier_columns(answers) -> dict:
    """A classifier's answers (``FrameResult``s) as columns."""
    return {"key": np.array([r.rid for r in answers], dtype=np.int64),
            "label": np.array([r.label for r in answers], dtype=np.int64),
            "logits": np.array([np.asarray(r.logits) for r in answers],
                               dtype=np.float64).reshape(len(answers), -1)}


def compare_classifier(h: Harness, name: str, cols, idx):
    """Labels and logits of a classifier's answers against the reference
    of their own frames: label mismatches, and the widest logit gap."""
    uniq, inv = np.unique(idx, return_inverse=True)
    rl, rlab = reference_outputs(h, name, uniq)
    lg = np.asarray(cols["logits"], dtype=np.float64)
    gap = np.abs(lg - rl[inv]).max() if len(idx) else 0.0
    return {"label_mismatch": (int((cols["label"] != rlab[inv]).sum()), 0),
            "logit_gap": (float(gap), 0)}


def classifier_control(h: Harness, name: str, acc: str):
    """The reference of a classifier in the system's place, its sums in
    ``acc``, over the whole bank: (columns, bank indices)."""
    idx = np.arange(len(h.bank))
    logits, labels = reference_outputs(h, name, idx, acc)
    return {"key": idx, "label": labels, "logits": logits}, idx
