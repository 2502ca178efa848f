"""A ``ServeFleet`` of one-chip replicas, one replica killed mid-window.

Cell keys: ``server.batch`` (each replica's batch), ``fleet.replicas``
(one per chip), ``fleet.kill`` (the replica to kill, or absent),
``fleet.kill_at`` (when, as a share of the window) and
``fleet.replace`` (bring a replacement up on the victim's chip).
"""

from __future__ import annotations

import harness


class Target:
    def __init__(self, h: harness.Harness, system: bool = True):
        from repro.serving import FaultInjector, ServeFleet
        (self.name, program), = h.programs.items()
        if not system:                 # the control: no system under test
            return

        class ClockKill(FaultInjector):
            """Kill the victim once the harness clock passes ``at``."""

            def __init__(self, victim, clock):
                super().__init__(victim)
                self.at, self.clock = float("inf"), clock

            def poll(self, fleet):
                if (not self.fired and self.clock() >= self.at
                        and self.victim in fleet.live_replicas):
                    self.fired = True
                    return self.victim
                return None

        opts = h.cell["fleet"]
        self.replicas = int(opts["replicas"])
        self.kill_at = float(opts.get("kill_at", 0.5))
        self.injector = (ClockKill(opts["kill"], h.clock)
                         if opts.get("kill") else None)
        kw = dict(batch=int(h.cell["server"]["batch"]))
        if h.interpret is not None:
            kw["interpret"] = h.interpret
        self.fleet = ServeFleet({self.name: program},
                                {self.name: h.artifacts[self.name]},
                                replicas=self.replicas,
                                devices=h.devices[:self.replicas],
                                injector=self.injector,
                                replace=bool(opts.get("replace", False)),
                                **kw)
        self.batch = self.fleet.batch
        self._seconds = h.seconds
        self._base = {}

    def warm(self, h: harness.Harness) -> None:
        """One full batch on every replica compiles each chip's program;
        a replacement reuses its chip's compiled function."""
        for k in range(self.batch * self.replicas):
            self.fleet.submit(self.name, h.frame(k)[1])
        self.fleet.drain()

    def capacity(self) -> int:
        return self.batch * len(self.fleet.live_replicas)

    def pending(self) -> int:
        return sum(len(self.fleet.replicas[n].queue)
                   for n in self.fleet.live_replicas)

    def _totals(self) -> dict:
        st = self.fleet.stats()
        return {"dispatches": st.dispatches, "served": st.total_served}

    def begin_window(self, t0: float) -> None:
        self._base = self._totals()
        if self.injector is not None:
            self.injector.at = t0 + self.kill_at * self._seconds

    def submit(self, frame) -> int:
        return self.fleet.submit(self.name, frame)

    def step(self):
        return self.fleet.step()

    def flush(self):
        return self.fleet.drain()

    def counters(self) -> dict:
        now = self._totals()
        st = self.fleet.stats()
        return {"dispatches": now["dispatches"] - self._base["dispatches"],
                "served": now["served"] - self._base["served"],
                "recovery_ms": st.recovery_ms,
                "failed": list(st.failed_replicas),
                "migrated": st.migrated_frames}

    def close(self) -> None:
        self.fleet.close()
        self.fleet = None

    columns = staticmethod(harness.classifier_columns)

    def compare(self, h: harness.Harness, cols, idx) -> dict:
        return harness.compare_classifier(h, self.name, cols, idx)

    def control_columns(self, h: harness.Harness, acc: str):
        """The reference in the system's place, its sums in ``acc``, over
        the whole bank: (columns, bank indices)."""
        return harness.classifier_control(h, self.name, acc)

    def work(self, h: harness.Harness, answers) -> dict:
        return {self.name: len(answers)}

