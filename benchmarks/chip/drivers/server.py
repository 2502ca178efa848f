"""One ``ChipServer`` serving the configuration's one program.

Cell keys (``server``): ``batch`` (the batch limit) and ``policy``.
Everything else (megakernel, prefetch, tiles, donation) is left to the
server's defaults, so a change of default is measured as it stands.
"""

from __future__ import annotations

import harness


class Target:
    def __init__(self, h: harness.Harness, system: bool = True):
        from repro.serving import ChipServer
        (self.name, program), = h.programs.items()
        if not system:                 # the control: no system under test
            return
        opts = h.cell["server"]
        kw = dict(batch=int(opts["batch"]), policy=opts.get("policy",
                                                            "static"))
        if h.interpret is not None:
            kw["interpret"] = h.interpret
        self.server = ChipServer({self.name: program},
                                 {self.name: h.artifacts[self.name]}, **kw)
        self.batch = self.server.batch

    def warm(self, h: harness.Harness) -> None:
        """Compile every dispatch size the policy can choose: the batch,
        or a bucketing policy's whole ladder."""
        sizes = getattr(self.server.policy, "_ladder", None) or (self.batch,)
        for n in sizes:
            for k in range(n):
                self.server.submit(self.name, h.frame(k)[1])
            self.server.drain()
        self.server.reset_stats()

    # -- the loops' surface ---------------------------------------------------

    def capacity(self) -> int:
        return self.batch

    def pending(self) -> int:
        return len(self.server.queue)

    def begin_window(self, t0: float) -> None:
        self.server.reset_stats()

    def submit(self, frame) -> int:
        return self.server.submit(self.name, frame)

    def step(self):
        return self.server.step()

    def flush(self):
        return self.server.drain()

    def counters(self) -> dict:
        st = self.server.stats()
        return {"dispatches": st.dispatches, "served": st.total_served,
                "padded": sum(st.padded.values())}

    def close(self) -> None:
        self.server.close()
        self.server = None

    # -- the check ------------------------------------------------------------

    columns = staticmethod(harness.classifier_columns)

    def compare(self, h: harness.Harness, cols, idx) -> dict:
        return harness.compare_classifier(h, self.name, cols, idx)

    def control_columns(self, h: harness.Harness, acc: str):
        """The reference in the system's place, its sums in ``acc``, over
        the whole bank: (columns, bank indices)."""
        return harness.classifier_control(h, self.name, acc)

    def work(self, h: harness.Harness, answers) -> dict:
        """Frames of each program computed for these answers."""
        return {self.name: len(answers)}
