"""Admin-plane demo and smoke: scrape a live warren's introspection API.

Builds a small ShardedWarren with an autopilot controller and an SLO
monitor, serves some traffic (so traces, latency histograms, and burn
gauges exist), demotes one group (so ``/tiered/runs`` has something to
say), then starts the :class:`repro.obs.AdminServer` and scrapes EVERY
endpoint, validating each response:

  * ``/metrics`` parses as Prometheus text 0.0.4 (cumulative histogram
    buckets, terminal ``+Inf`` equal to ``_count``);
  * ``/profile/cpu`` returns non-empty collapsed stacks;
  * ``/routing``, ``/traces``, ``/autopilot/decisions``, ``/slo``,
    ``/tiered/runs``, ``/healthz``, ``/readyz``, ``/metrics.json`` all
    answer 200 with well-formed JSON.

Exits non-zero on any failed check — this is the CI ``admin-smoke`` job.

Run:  PYTHONPATH=src python examples/admin_demo.py
"""

import json
import math
import sys
import tempfile
import threading
import urllib.request

from repro import obs
from repro.core import ingest_documents, score_bm25
from repro.data.synth import doc_generator
from repro.dist.autopilot import (AutopilotConfig, ColdPolicy, Controller,
                                  HotSplitPolicy, Hysteresis)
from repro.dist.shard_router import ShardedWarren
from repro.dist.simharness import SimClock

QUERIES = ["school education student", "government law state",
           "stock money business", "vibration conductor wind"]

failures = []


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  {'PASS' if ok else 'FAIL'}  {name}" +
          (f" ({detail})" if detail else ""))
    if not ok:
        failures.append(name)


def get(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read().decode()


def check_prometheus(text: str) -> None:
    """Format-0.0.4 conformance over the live scrape."""
    histograms = set()
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE") and line.endswith("histogram"):
            histograms.add(line.split()[2])
    check("metrics: at least one histogram family", bool(histograms))
    # per histogram series: cumulative buckets end at +Inf == _count
    counts, infs = {}, {}
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            continue
        metric, value = line.rsplit(" ", 1)
        name = metric.split("{")[0]
        base = name[:-7] if name.endswith("_bucket") else \
            name[:-6] if name.endswith("_count") else None
        if base not in histograms:
            continue
        series = metric.split("{")[1].rstrip("}") if "{" in metric else ""
        labels = tuple(sorted(p.rstrip('"') for p in series.split('",')
                              if p and not p.startswith('le="')))
        if name.endswith("_bucket") and 'le="+Inf"' in series:
            infs[(base, labels)] = float(value)
        elif name.endswith("_count"):
            counts[(base, labels)] = float(value)
    check("metrics: every histogram series has a +Inf bucket",
          set(counts) == set(infs),
          f"{len(counts)} series")
    check("metrics: +Inf bucket == _count everywhere",
          all(infs[k] == counts[k] for k in counts))


def main() -> int:
    obs.enable()
    static_root = tempfile.mkdtemp(prefix="admin-demo-")
    warren = ShardedWarren(n_shards=2, replicas=2, static_dir=static_root)
    ingest_documents(warren, doc_generator(7, 150, mean_len=30), batch=8)

    clock = SimClock()
    monitor = obs.SLOMonitor(clock=clock)
    ctl = Controller.for_warren(warren, clock=clock, slo_monitor=monitor,
                                config=AutopilotConfig(
        split=HotSplitPolicy(p95_hot_ms=0.0, sustain_ticks=2, min_docs=1,
                             max_groups=3),
        cold=ColdPolicy(demote_after_ticks=10 ** 6,
                        merge_after_ticks=10 ** 6),
        hysteresis=Hysteresis(cooldown_ticks=1, min_dwell_ticks=0),
        pool=None))

    # traffic -> traces + latency histograms + a split decision
    for _ in range(3):
        with warren:
            for q in QUERIES:
                score_bm25(warren, q, k=10)
        ctl.tick()
        clock.advance()
    warren.demote_group(0)                  # /tiered/runs has content

    # background load so /profile/cpu has stacks to sample
    stop = threading.Event()

    def load():
        while not stop.is_set():
            with warren:
                score_bm25(warren, QUERIES[0], k=10)

    loader = threading.Thread(target=load, name="load", daemon=True)
    loader.start()

    with obs.AdminServer(warren=warren, controller=ctl,
                         slo=monitor) as srv:
        print(f"admin endpoint: {srv.url()}")

        code, body = get(srv.url("/healthz"))
        check("/healthz", code == 200 and json.loads(body)["ok"] is True)

        code, body = get(srv.url("/readyz"))
        doc = json.loads(body)
        check("/readyz", code == 200 and doc["ready"] is True,
              f"epoch {doc.get('epoch')}")

        code, text = get(srv.url("/metrics"))
        check("/metrics answers", code == 200 and len(text) > 0)
        check_prometheus(text)
        check("/metrics: slo_burn_rate exported",
              "slo_burn_rate" in text)
        # ProfiledLock registers its series at construction, so the
        # group write locks show up even before any contention
        check("/metrics: lock contention family present",
              "lock_wait_ms" in text)

        code, body = get(srv.url("/metrics.json"))
        doc = json.loads(body)
        check("/metrics.json",
              code == 200 and "scatter_latency_ms" in doc["metrics"])

        code, body = get(srv.url("/routing"))
        doc = json.loads(body)
        check("/routing", code == 200 and doc["n_groups"] == warren.n_shards
              and all(g["ranges"] for g in doc["groups"].values()),
              f"{doc['n_groups']} groups, epoch {doc['epoch']}")

        code, body = get(srv.url("/traces"))
        traces = json.loads(body)["traces"]
        check("/traces", code == 200 and len(traces) > 0,
              f"{len(traces)} in ring")
        tid = traces[-1]["trace_id"]
        code, body = get(srv.url(f"/traces/{tid}"))
        check("/traces/<id>",
              code == 200 and json.loads(body)["tree"]["name"])

        code, body = get(srv.url("/autopilot/decisions?n=10"))
        doc = json.loads(body)
        check("/autopilot/decisions",
              code == 200 and doc["tick"] >= 3,
              f"{len(doc['decisions'])} decisions")

        code, body = get(srv.url("/tiered/runs"))
        doc = json.loads(body)
        check("/tiered/runs",
              code == 200 and doc["demoted_groups"],
              f"demoted: {sorted(doc['demoted_groups'])}")

        code, body = get(srv.url("/slo"))
        doc = json.loads(body)
        names = [s["name"] for s in doc["slos"]]
        check("/slo", code == 200 and "serving_p95" in names,
              f"slos: {names}")

        code, text = get(srv.url("/profile/cpu?seconds=0.5"))
        lines = [ln for ln in text.strip().split("\n") if ln]
        ok_fmt = bool(lines) and all(
            ln.rsplit(" ", 1)[1].isdigit() for ln in lines)
        check("/profile/cpu returns non-empty collapsed stacks",
              code == 200 and ok_fmt, f"{len(lines)} stacks")

    stop.set()
    loader.join(timeout=10.0)
    warren.close()

    if failures:
        print(f"\n{len(failures)} admin-smoke check(s) FAILED: {failures}")
        return 1
    print("\nall admin-smoke checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
