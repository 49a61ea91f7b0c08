"""One run of one cell: set-up, warm-up, the window, the check.

``run`` is what ``bench/run.py`` calls.  Its ``platform`` and ``hooks``
arguments exist for ``bench/tests``, which rehearse a run on the CPU and
break the program underneath it; the command line always asks for a TPU.

What depends on what a configuration deploys is its kind's
(``"kind"`` in the configuration's file; ``cells.kind`` loads
``bench/kinds/<kind>``).  A kind provides:

- ``REQUESTS``: the request kinds it serves, in order, each ``"read"`` or
  ``"write"``; a mix that names another is refused;
- ``open(cell, state_dir, log_dir, span)``: the deployment, built or
  restored, as a :class:`Deployment`;
- ``payloads(mix, config, counts, stream)``: what each of ``counts[k]``
  requests of kind ``k`` carries, as lists; ``generate`` deals them out
  in the order and at the times the seed draws;
- ``submit(server, payload)``: send a read, returning a handle with
  ``get``; writes go to the deployment's ``write``;
- ``warm(session, slack)``: compile every device shape the session's
  pool of reads can reach; returns what it warmed, for the log;
- ``check(cell, session, window, seed)``: ``{name: {"value", "limit"}}``,
  each number compared beside its limit; a run is correct where every
  value is within its limit;
- ``context(session)``: the fields the per-layer readers read beyond the
  window's own (``Context``).
"""

from __future__ import annotations

import contextlib
import gc
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

import cells
import device
import drive
import generate
import trace as trace_mod

WARM_STREAM, WINDOW_STREAM = 1, 0
# snapshots, durable logs and traces
STATE = cells.BENCH / ".state"


def log(msg: str) -> None:
    import sys
    print(msg, file=sys.stderr, flush=True)


def _obs_series(name: str) -> Dict[tuple, tuple]:
    from repro import obs
    out = {}
    for labels, m in obs.registry().series(name):
        out[tuple(sorted(labels.items()))] = (m.count, m.sum)
    return out


def _delta(after: Dict[tuple, tuple], before: Dict[tuple, tuple]
           ) -> Dict[tuple, tuple]:
    return {k: (c - before.get(k, (0, 0.0))[0], s - before.get(k, (0, 0.0))[1])
            for k, (c, s) in after.items()}


@contextlib.contextmanager
def trace_labels(enabled: bool):
    """With ``enabled``, every ``repro.obs.span`` and ``phase_timer`` the
    program enters also writes a ``jax.profiler.TraceAnnotation`` of the
    same name, so the device trace can say what the host was doing."""
    if not enabled:
        yield
        return
    import jax
    from repro import obs
    span0, phase0 = obs.span, obs.phase_timer

    @contextlib.contextmanager
    def span(name, **labels):
        with jax.profiler.TraceAnnotation(name), span0(name, **labels) as s:
            yield s

    @contextlib.contextmanager
    def phase_timer(kernel, phase):
        with jax.profiler.TraceAnnotation(f"{kernel}.{phase}"), \
                phase0(kernel, phase):
            yield

    obs.span, obs.phase_timer = span, phase_timer
    try:
        yield
    finally:
        obs.span, obs.phase_timer = span0, phase0


class Deployment:
    """What a kind's ``open`` returns: the system under test (``server``,
    serving reads through the program's ``MicroBatcher`` as
    ``server.batcher`` and keeping ``server.timings``), its set-up
    timings, and the writes its writers record.  A kind that serves
    writes overrides ``write``, ``drain``, ``errors`` and ``stop``."""

    def __init__(self, server, times: dict):
        self.server, self.times = server, times
        self.writes = drive.Writes([], [], [], [], [], [], [])

    def write(self, at: float, payload: Any) -> None:
        raise NotImplementedError("this deployment takes no writes")

    def drain(self, timeout: float) -> bool:
        """Wait until every write handed over is committed or failed."""
        return True

    @property
    def errors(self) -> List[BaseException]:
        return []

    def stop(self) -> None:
        """Stop serving; the state stays for the check."""
        self.server.close()

    def close(self) -> None:
        self.stop()


class FullCollections:
    """Times every full (generation 2) garbage collection while open: a
    pause of the whole process that the generator's lateness shows too."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t0 = None

    def _note(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)
        return False


def n_updates(plan) -> int:
    return sum(u is not None for u in getattr(plan, "updates", []))


def pct(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if len(values) else float("nan")


class Session:
    """A cell's deployment and server, set up once, and the windows driven
    through them.  ``bench/run.py`` drives one window per process; the
    rate sweep and the control drive several."""

    def __init__(self, workload: str, traced: bool = False,
                 platform: str = "tpu", root: Path = cells.ROOT,
                 hooks: Optional[dict] = None):
        cell = self.cell = cells.load(workload, root)
        self.kind = cells.kind(cell.config["kind"], root)
        generate.shares(cell.mix, self.kind)
        self.dev = device.check(cell.chips, platform)
        log(f"device: {self.dev}")
        log(f"compile cache: {device.enable_cache()}")
        import jax
        from repro import obs
        self.compiles = device.CompileCounter()
        obs.enable() if traced else obs.disable()
        # every read the session's plans send, in order of registration
        self.pool: List[Any] = []
        self.span = ((lambda name: jax.profiler.TraceAnnotation(name))
                     if traced else drive._noop_span)
        self.dep = self.kind.open(cell, STATE, STATE / "logs" / cell.name,
                                  self.span)
        log(f"deployment: {self.dep.times}")
        self.server = self.dep.server
        self.clock = drive.BatchClock(self.server.batcher)
        if hooks and "server" in hooks:
            self.server = hooks["server"](self.server)

    def plan(self, seed: int, seconds: float, stream: int,
             mix: Optional[dict] = None):
        """The requests of one window of ``mix`` (the cell's by default),
        its reads added to the session's pool."""
        p = generate.plan(mix or self.cell.mix, self.kind, self.cell.config,
                          seed, seconds, stream)
        off = len(self.pool)
        self.pool.extend(p.queries)
        if isinstance(p, generate.ClosedPlan):
            p.order = p.order + off
        else:
            p.query = np.where(p.query >= 0, p.query + off, -1)
        return p

    def warm(self, plan, slack: int) -> None:
        """Compile every device shape that the reads planned so far can
        reach while the deployment changes by no more than ``slack``
        writes, then drive ``plan``, the warm-up's own requests."""
        t0 = time.perf_counter()
        what = self.kind.warm(self, slack)
        c = self.compiles
        log(f"warm-up: {what} in "
            f"{time.perf_counter() - t0:.3f}s ({c.compiles} backend "
            f"compiles, {c.cache_hits} persistent cache hits)")
        before = c.compiles
        reads, _ = self.drive(plan, self.cell.mix["warmup_s"],
                              time.perf_counter() + 0.05)
        self.dep.drain(drive.GRACE_S)
        log(f"warm-up traffic: {len(reads.done)} reads, "
            f"{len(self.dep.writes.ack)} writes, "
            f"{c.compiles - before} compiles")
        # the restored heap is millions of objects: one full collection
        # now, as a server long past its start would have had, so that the
        # collection the load leaves pending does not fall in the window
        t0 = time.perf_counter()
        gc.collect()
        log(f"full collection after set-up: {time.perf_counter() - t0:.3f}s")

    def drive(self, plan, seconds: float, t0: float):
        """Send ``plan``'s requests from ``t0``: its reads through the
        kind's ``submit``, its writes to the deployment."""
        submit, server, pool = self.kind.submit, self.server, self.pool

        def send(q):
            return submit(server, pool[q])
        if isinstance(plan, generate.ClosedPlan):
            return drive.closed_loop(send, plan, t0, seconds)
        return drive.open_loop(send, plan, t0, self.dep.write, self.span)

    def window(self, plan, seconds: float, traced: bool = False
               ) -> "Window":
        """Drive ``plan`` for ``seconds``."""
        import jax
        dep, server = self.dep, self.server
        w_lo = len(dep.writes.ack)
        b_lo = len(self.clock.batches)
        compiles_before = self.compiles.compiles
        timings0 = server.timings.snapshot()
        batch0 = _obs_series("serve_batch_size")
        phase0 = _obs_series("kernel_phase_ms")
        trace_dir = STATE / "trace" / self.cell.name
        if traced:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            # host TraceMe events and device activity; no Python tracer,
            # which writes an event per Python call
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        t0 = time.perf_counter() + 0.05
        with trace_labels(traced), span_ctx(traced, "bench.window"), \
                FullCollections() as full:
            reads, late = self.drive(plan, seconds, t0)
            dep.drain(max(1.0, t0 + seconds + drive.GRACE_S
                          - time.perf_counter()))
        if traced:
            jax.profiler.stop_trace()
        win = Window(self, reads, w_lo, seconds, t0, late)
        win.pins = self.clock.pins(reads, b_lo)
        win.compiles = self.compiles.compiles - compiles_before
        win.timings = (timings0, server.timings.snapshot())
        win.batch = _delta(_obs_series("serve_batch_size"), batch0)
        win.phase = _delta(_obs_series("kernel_phase_ms"), phase0)
        win.memory = device.memory_peak_bytes(self.cell.chips)
        win.trace_dir = trace_dir if traced else None
        log(f"full collections in the window: {len(full.pauses)}, "
            f"seconds {[round(p, 3) for p in full.pauses]}")
        log(f"window: {len(reads.done)} reads, "
            f"{len(dep.writes.ack) - w_lo} writes; generator lateness "
            f"{late}; compiles in the window {win.compiles}"
            + (f" {self.compiles.names[compiles_before:]}"
               if win.compiles else ""))
        log(f"requests: {win.attempted} attempted, {win.failed} failed; "
            + ", ".join(f"{k} {v:.3f}" for k, v in win.e2e.items()))
        errors = dep.errors
        if errors:
            log(f"writes failed: {len(errors)}, first: {errors[0]!r}")
        return win

    def stop_serving(self) -> None:
        self.dep.stop()

    def check(self, win: "Window", seed: int) -> dict:
        return self.kind.check(self.cell, self, win, seed)

    def close(self) -> None:
        self.dep.close()


class Window:
    """One driven window: its requests and the program's counters."""

    def __init__(self, session, reads, w_lo, seconds, t0, late):
        self.reads, self.w_lo, self.seconds = reads, w_lo, seconds
        self.late = late
        writes = session.dep.writes
        ack = np.array(writes.ack[w_lo:], dtype=np.float64)
        due = np.array(writes.due[w_lo:], dtype=np.float64)
        ok_w = ~np.isnan(ack)
        ok_r = ~np.isnan(reads.done)
        lat_r = 1e3 * (reads.done[ok_r] - reads.due[ok_r])
        lat_w = 1e3 * (ack[ok_w] - due[ok_w])
        self.e2e = {
            "query_p50_ms": pct(lat_r, 50),
            "query_p99_ms": pct(lat_r, 99),
            "commit_p50_ms": pct(lat_w, 50),
            "commit_p95_ms": pct(lat_w, 95),
            "queries_per_s": float((reads.done[ok_r] <= t0 + seconds).sum())
            / seconds,
        }
        self.attempted = len(reads.done) + len(ack)
        self.failed = int((~ok_r).sum() + (~ok_w).sum())


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: Optional[float] = None, platform: str = "tpu",
        root: Path = cells.ROOT, hooks: Optional[dict] = None) -> dict:
    """One run: set-up, warm-up, one window, the check; the result line."""
    t_start = time.time() if t_start is None else t_start
    s = Session(workload, traced, platform, root, hooks)
    try:
        warm = s.plan(seed, s.cell.mix["warmup_s"], WARM_STREAM)
        plan = s.plan(seed, seconds, WINDOW_STREAM)
        s.warm(warm, n_updates(warm) + n_updates(plan))
        setup_s = time.time() - t_start
        win = s.window(plan, seconds, traced)
        # the program's state is freed before the reference runs
        s.stop_serving()
        checks = s.check(win, seed)
    finally:
        s.close()
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": win.attempted, "failed": win.failed}
    dev = dict(s.dev, memory_peak_bytes=win.memory)
    if traced:
        red = trace_mod.reduce(trace_mod.find_xplane(win.trace_dir))
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        ctx = Context(reads=win.reads, writes=s.dep.writes, w_lo=win.w_lo,
                      batch=win.batch, phase=win.phase, timings=win.timings,
                      trace=red, peaks=device.peaks(s.dev["kind"]),
                      **s.kind.context(s))
        metrics = {}
        for m in s.cell.per_layer:
            v = cells.reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out.update(metrics=metrics, device=dev,
                   breakdown={"device_ops": red["device_ops"],
                              "idle_gaps": red["idle_gaps"]})
    else:
        metrics = {m["name"]: {"value": win.e2e[m["name"]], "unit": m["unit"]}
                   for m in s.cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        out.update(metrics=metrics, device=dev)
    out["checks"] = checks
    return out


@contextlib.contextmanager
def span_ctx(traced: bool, name: str):
    if traced:
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


class Context:
    """What a per-layer reader may read; see ``bench/metrics``.  The
    kind's ``context`` adds its own fields, ``kernel`` among them: the
    device kernel whose ``kernel_phase_ms`` phases ``phase_ms`` reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def n_batches(self) -> int:
        return int(sum(c for c, _ in self.batch.values()))

    def phase_ms(self, phase: str) -> Optional[float]:
        for labels, (c, s) in self.phase.items():
            if dict(labels) == {"kernel": self.kernel, "phase": phase}:
                return s
        return None
