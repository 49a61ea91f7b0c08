"""One run of one cell: set-up, warm-up, the window, the check.

``run`` is what ``bench/run.py`` calls.  Its ``platform`` and ``hooks``
arguments exist for ``bench/tests``, which rehearse a run on the CPU and
break the program underneath it; the command line always asks for a TPU.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import cells
import check
import corpus as corpus_mod
import deploy
import device
import drive
import generate
import trace as trace_mod

WARM_STREAM, WINDOW_STREAM = 1, 0
COMPILE_THREADS = 8
RTOL = 1e-5


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


def device_shapes(server, warren, pool: List[List[int]], slack: int) -> set:
    """Every ``(qp, tp, l, nb)`` block shape the server can score for
    batches drawn from ``pool``, using the server's own bucketing, when
    no posting list or group grows or shrinks by more than ``slack``
    documents."""
    from repro.core import ranking
    feats = [[ranking.TF_PREFIX + ranking.porter_stem(corpus_mod.word(r))
              for r in q] for q in pool]
    uniq = sorted({f for q in feats for f in q})
    with warren:
        per_group = warren.map_groups(lambda w: (
            len(w.annotations(ranking.DOC_FEATURE)),
            [len(w.annotations(f)) for f in uniq]))
    max_batch = server.batcher.cfg.max_batch
    qps = {server._pad_sizes(n, 1, 1)[0] for n in range(1, max_batch + 1)}
    tps = {server._pad_sizes(1, len(q[:server.max_terms]), 1)[1]
           for q in feats}
    shapes = set()
    for n_g, dfs in per_group:
        df = dict(zip(uniq, dfs))
        ls = set()
        for q in feats:
            longest = max(df[f] for f in q)
            if longest == 0 and not slack:
                continue
            for d in range(max(1, longest - slack), longest + slack + 1, 64):
                ls.add(server._pad_sizes(1, 1, d)[2])
            ls.add(server._pad_sizes(1, 1, longest + slack)[2])
        nbs = {server._acc_pad(n)
               for n in range(max(0, n_g - slack), n_g + slack + 1)}
        for qp in qps:
            for tp in tps:
                for l in ls:
                    for nb in nbs:
                        shapes.add((qp, tp, l, nb))
    return shapes


def compile_shapes(shapes: set, k: int) -> None:
    """Compile the served scorer for every shape, several at once, then
    run each once so the window finds all of them ready."""
    import jax
    import jax.numpy as jnp
    from repro.train import serve

    def one(shape):
        qp, tp, l, nb = shape
        serve.bm25_topk.lower(
            jax.ShapeDtypeStruct((qp, tp, l), jnp.int32),
            jax.ShapeDtypeStruct((qp, tp, l), jnp.float32),
            jax.ShapeDtypeStruct((qp, tp), jnp.float32),
            n_docs=nb, k=k).compile()

    with ThreadPoolExecutor(COMPILE_THREADS) as ex:
        list(ex.map(one, sorted(shapes)))
    out = None
    for qp, tp, l, nb in sorted(shapes):
        out = serve.bm25_topk(
            jnp.asarray(np.full((qp, tp, l), nb, np.int32)),
            jnp.asarray(np.zeros((qp, tp, l), np.float32)),
            jnp.asarray(np.zeros((qp, tp), np.float32)), n_docs=nb, k=k)
    if out is not None:
        jax.block_until_ready(out)


class Traffic:
    """Drives planned requests through the server (and writers)."""

    def __init__(self, cell, server, warren, addrs, pool_texts, span):
        self.cell, self.server, self.warren = cell, server, warren
        self.pool_texts = pool_texts
        self.span = span
        self.versions = drive.Versions(addrs)
        self.writes = drive.Writes([], [], [], [], [], [], [])
        self.lock = threading.Lock()
        self.writers: List[drive.Writer] = []
        mix = cell.mix
        if generate.shares(mix).get("update", 0.0) > 0:
            def text_of(ranks):
                return " ".join(corpus_mod.word(int(r)) for r in ranks)
            self.writers = [drive.Writer(warren.clone(), self.versions,
                                         self.writes, self.lock, text_of,
                                         span)
                            for _ in range(mix["update"]["writers"])]
            drive.watch_publish(warren, self.writers)
            for w in self.writers:
                w.start()

    def drive(self, plan, seconds: float, t0: float):
        if isinstance(plan, generate.ClosedPlan):
            return drive.closed_loop(self.server, plan, self.pool_texts, t0,
                                     seconds)
        return drive.open_loop(self.server, plan, self.pool_texts, t0,
                               self.writers, self.span)

    def drain(self, timeout: float) -> bool:
        """Wait until every queued update is committed or failed."""
        end = time.perf_counter() + timeout
        while any(w.q.unfinished_tasks for w in self.writers):
            if time.perf_counter() > end:
                return False
            time.sleep(0.01)
        return True

    def close(self):
        for w in self.writers:
            w.q.put(None)
        for w in self.writers:
            w.join(timeout=drive.GRACE_S)
        self.writers = []


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
        self.dev = device.check(cell.chips, platform)
        log(f"device: {self.dev}")
        log(f"compile cache: {device.enable_cache()}")
        import jax
        from repro import obs
        from repro.train.serve import BatcherConfig, RetrievalServer
        self.compiles = device.CompileCounter()
        obs.enable() if traced else obs.disable()
        cfg = cell.config
        srv, dep = cfg["server"], cfg["deployment"]
        self.corpus = corpus_mod.make_corpus(cfg)
        # every query the session's plans send, in order of registration
        self.pool: List[List[int]] = []
        self.pool_texts: List[str] = []
        self.log_dir = (deploy.STATE / "logs" / cell.name
                        if dep["durable_log"] else None)
        self.warren, self.addrs, times = deploy.open_deployment(
            cfg, self.corpus, self.log_dir)
        log(f"deployment: {times}")
        self.server = RetrievalServer(
            self.warren, k=srv["k"], max_terms=srv["max_terms"],
            max_postings=srv["max_postings"],
            batcher=BatcherConfig(max_batch=srv["max_batch"],
                                  max_wait_ms=srv["max_wait_ms"]))
        self.clock = drive.BatchClock(self.server.batcher)
        if hooks and "server" in hooks:
            self.server = hooks["server"](self.server)
        span = ((lambda name: jax.profiler.TraceAnnotation(name)) if traced
                else drive._noop_span)
        self.traffic = Traffic(cell, self.server, self.warren, self.addrs,
                               self.pool_texts, span)

    def plan(self, seed: int, seconds: float, stream: int,
             mix: Optional[dict] = None):
        """The requests of one window of ``mix`` (the cell's by default),
        its queries added to the session's pool."""
        p = generate.plan(mix or self.cell.mix, self.cell.config, self.corpus,
                          seed, seconds, stream)
        off = len(self.pool)
        self.pool.extend(p.queries)
        self.pool_texts.extend(corpus_mod.query_text(q) for q in p.queries)
        if isinstance(p, generate.ClosedPlan):
            p.order = p.order + off
        else:
            p.query = np.where(p.query >= 0, p.query + off, -1)
        return p

    def warm(self, plan, slack: int) -> None:
        """Compile every device shape that the queries planned so far can
        reach while no list or group changes by more than ``slack``
        documents, then drive ``plan``, the warm-up's own requests."""
        t0 = time.perf_counter()
        shapes = device_shapes(self.server, self.warren, self.pool, slack)
        compile_shapes(shapes, self.cell.config["server"]["k"])
        c = self.compiles
        log(f"warm-up: {len(shapes)} device shapes in "
            f"{time.perf_counter() - t0:.3f}s ({c.compiles} backend "
            f"compiles, {c.cache_hits} persistent cache hits)")
        before = c.compiles
        reads, _ = self.traffic.drive(plan, self.cell.mix["warmup_s"],
                                      time.perf_counter() + 0.05)
        self.traffic.drain(drive.GRACE_S)
        log(f"warm-up traffic: {len(reads.done)} reads, "
            f"{len(self.traffic.writes.ack)} writes, "
            f"{c.compiles - before} compiles")
        # the restored heap is millions of objects: one full collection
        # now, as a server long past its start would have had, so that the
        # collection the load leaves pending does not fall in the window
        t0 = time.perf_counter()
        gc.collect()
        log(f"full collection after set-up: {time.perf_counter() - t0:.3f}s")

    def window(self, plan, seconds: float, traced: bool = False
               ) -> "Window":
        """Drive ``plan`` for ``seconds``."""
        import jax
        traffic, server = self.traffic, self.server
        w_lo = len(traffic.writes.ack)
        b_lo = len(self.clock.batches)
        compiles_before = self.compiles.compiles
        timings0 = server.timings.snapshot()
        batch0 = _obs_series("serve_batch_size")
        phase0 = _obs_series("kernel_phase_ms")
        trace_dir = deploy.STATE / "trace" / self.cell.name
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
            reads, late = traffic.drive(plan, seconds, t0)
            traffic.drain(max(1.0, t0 + seconds + drive.GRACE_S
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
            f"{len(traffic.writes.ack) - w_lo} writes; generator lateness "
            f"{late}; compiles in the window {win.compiles}"
            + (f" {self.compiles.names[compiles_before:]}"
               if win.compiles else ""))
        log(f"requests: {win.attempted} attempted, {win.failed} failed; "
            + ", ".join(f"{k} {v:.3f}" for k, v in win.e2e.items()))
        errors = [e for w in traffic.writers for e in w.errors]
        if errors:
            log(f"writes failed: {len(errors)}, first: {errors[0]!r}")
        return win

    def stop_serving(self) -> None:
        self.traffic.close()
        self.server.close()

    def check(self, win: "Window", seed: int) -> dict:
        return check.run(self.cell, self.corpus, self.pool, self.addrs,
                         win.reads, win.pins, self.traffic.writes,
                         self.warren, seed, RTOL, self.log_dir)

    def close(self) -> None:
        self.stop_serving()
        self.warren.close()


class Window:
    """One driven window: its requests and the program's counters."""

    def __init__(self, session, reads, w_lo, seconds, t0, late):
        self.reads, self.w_lo, self.seconds = reads, w_lo, seconds
        self.late = late
        writes = session.traffic.writes
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
        ctx = Context(reads=win.reads, writes=s.traffic.writes,
                      w_lo=win.w_lo, pool=s.pool,
                      corpus_df=corpus_mod.document_frequency(s.corpus),
                      batch=win.batch, phase=win.phase, timings=win.timings,
                      trace=red, peaks=device.peaks(s.dev["kind"]))
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
    """What a per-layer reader may read; see ``bench/metrics``."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def n_batches(self) -> int:
        return int(sum(c for c, _ in self.batch.values()))

    def phase_ms(self, phase: str) -> Optional[float]:
        for labels, (c, s) in self.phase.items():
            if dict(labels) == {"kernel": "bm25_topk", "phase": phase}:
                return s
        return None
