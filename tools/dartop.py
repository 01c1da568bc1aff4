"""dartop — live terminal dashboard over the DART serving metrics.

Reads the Prometheus text exposition the obs registry exports (either
the ``--file`` a running server writes via ``obs.configure(textfile=
...)``, or ``--url http://host:port/metrics`` from ``obs.configure(
http_port=...)``) and renders, per refresh:

* per-lane request latency p50/p95 (estimated from the
  ``dart_request_latency_ms`` histogram buckets) + completion counts;
* per-member exit-depth histograms (``dart_exits_total``), the paper's
  Alg. 1 outcome distribution;
* per-lane DAES / speedup / power-efficiency (Eq. 9, Eqs. 20-22);
* slot-pool / KV-page occupancy (continuous batching);
* shed / rejection / starvation / escalation rates and — alertable —
  recompile and xla-fallback counters.

Usage:
    python tools/dartop.py --file metrics.prom
    python tools/dartop.py --url http://127.0.0.1:9099/metrics
    python tools/dartop.py --once --json --file metrics.prom

``--once`` renders a single frame and exits (non-zero if the source is
missing or unparseable); ``--json`` emits the parsed summary instead of
the ANSI view, for scripts.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import urllib.request

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.obs.metrics import estimate_percentile, parse_prometheus


# ---------------------------------------------------------------------------
# scrape
# ---------------------------------------------------------------------------

def scrape(args) -> dict:
    """One scrape -> parse_prometheus families."""
    if args.url:
        with urllib.request.urlopen(args.url, timeout=5) as r:
            text = r.read().decode()
    else:
        text = pathlib.Path(args.file).read_text()
    return parse_prometheus(text)


def _series(fams: dict, name: str) -> list:
    """[(labels, value), ...] of the base samples of one family."""
    fam = fams.get(name)
    if not fam:
        return []
    return [(labels, v) for n, labels, v in fam["samples"] if n == name]


def _value(fams: dict, name: str, **match) -> float:
    for labels, v in _series(fams, name):
        if all(labels.get(k) == str(w) for k, w in match.items()):
            return v
    return 0.0


# ---------------------------------------------------------------------------
# summarize (shared by the ANSI view and --json)
# ---------------------------------------------------------------------------

def _lane_latency(fams: dict) -> dict:
    """lane -> {p50, p95, count} from dart_request_latency_ms buckets."""
    fam = fams.get("dart_request_latency_ms")
    if not fam:
        return {}
    per_lane: dict = {}
    for name, labels, v in fam["samples"]:
        lane = labels.get("lane", "")
        d = per_lane.setdefault(lane, {"buckets": [], "count": 0.0})
        if name.endswith("_bucket"):
            le = labels["le"]
            d["buckets"].append((float("inf") if le == "+Inf"
                                 else float(le), v))
        elif name.endswith("_count"):
            d["count"] = v
    out = {}
    for lane, d in per_lane.items():
        bs = sorted(d["buckets"])
        edges = [le for le, _ in bs if le != float("inf")]
        cum = [c for _, c in bs]
        # cumulative -> per-bucket (incl. +Inf overflow)
        counts = [cum[0]] + [cum[i] - cum[i - 1]
                             for i in range(1, len(cum))]
        if not edges:
            continue
        out[lane] = {"p50": estimate_percentile(edges, counts, 50),
                     "p95": estimate_percentile(edges, counts, 95),
                     "count": int(d["count"])}
    return out


def _exit_hists(fams: dict) -> dict:
    """member -> {stage: count} from dart_exits_total."""
    out: dict = {}
    for labels, v in _series(fams, "dart_exits_total"):
        out.setdefault(labels.get("member", "0"), {})[
            labels.get("stage", "?")] = int(v)
    return out


def summarize(fams: dict) -> dict:
    lanes = {}
    for labels, v in _series(fams, "dart_lane_daes"):
        lanes.setdefault(labels["lane"], {})["daes"] = v
    for col in ("speedup", "power_eff", "acc_pct", "n"):
        for labels, v in _series(fams, f"dart_lane_{col}"):
            lanes.setdefault(labels["lane"], {})[col] = v
    occupancy = {k: _value(fams, f"dart_{k}") for k in
                 ("slots_total", "slots_in_use", "pages_total",
                  "pages_in_use", "pages_peak")
                 if f"dart_{k}" in fams}
    sched = {labels["event"]: int(v) for labels, v in
             _series(fams, "dart_scheduler_events_total")}
    recompiles = sum(v for _, v in _series(fams, "dart_recompiles_total"))
    fallbacks = sum(v for labels, v in
                    _series(fams, "dart_kernel_dispatch_total")
                    if labels.get("backend") == "xla")
    errors = {labels.get("component", "?"): int(v) for labels, v in
              _series(fams, "dart_errors_total")}
    health = {labels.get("engine", "?"): int(v) for labels, v in
              _series(fams, "dart_engine_health")}
    faults = {f"{labels.get('point', '?')}/{labels.get('kind', '?')}": int(v)
              for labels, v in _series(fams, "dart_faults_injected_total")
              if labels.get("point") != "_all"}

    def _total(name: str, agg_label: str, agg_value: str) -> int:
        # The pool publishes both per-event push samples and one
        # authoritative aggregate row (engine="_pool" / point="_all");
        # prefer the aggregate, fall back to summing the push samples.
        rows = _series(fams, name)
        agg = [v for labels, v in rows if labels.get(agg_label) == agg_value]
        if agg:
            return int(sum(agg))
        return int(sum(v for labels, v in rows))

    resilience = {
        "engine_health": health,
        "degradation_rung": int(_value(fams, "dart_degradation_rung")),
        "retries": _total("dart_retries_total", "engine", "_pool"),
        "hedges": _total("dart_hedges_total", "engine", "_pool"),
        "requeues": int(sum(v for _, v in
                            _series(fams, "dart_requeues_total"))),
        "faults_injected": faults,
        "pool_events": {labels.get("event", "?"): int(v) for labels, v in
                        _series(fams, "dart_pool_events_total")},
    }
    return {"latency_ms": _lane_latency(fams),
            "exits": _exit_hists(fams),
            "lanes": lanes,
            "occupancy": occupancy,
            "scheduler": sched,
            "queued": {labels["lane"]: v for labels, v in
                       _series(fams, "dart_queue_depth")},
            "escalations": {labels["member"]: int(v) for labels, v in
                            _series(fams, "dart_escalations_total")},
            "recompiles": int(recompiles),
            "xla_fallbacks": int(fallbacks),
            "errors": errors,
            "resilience": resilience}


# ---------------------------------------------------------------------------
# render
# ---------------------------------------------------------------------------

def _bar(frac: float, width: int = 24) -> str:
    n = int(round(min(max(frac, 0.0), 1.0) * width))
    return "#" * n + "." * (width - n)


def render(s: dict) -> str:
    L = ["=== dartop ==="]
    if s["latency_ms"]:
        L.append("-- latency (ms) --")
        for lane in sorted(s["latency_ms"]):
            d = s["latency_ms"][lane]
            L.append(f"  lane {lane:>12}  p50 {d['p50']:8.2f}  "
                     f"p95 {d['p95']:8.2f}  n={d['count']}")
    if s["exits"]:
        L.append("-- exit depth (Alg. 1) --")
        for m in sorted(s["exits"]):
            hist = s["exits"][m]
            total = sum(hist.values()) or 1
            for stage in sorted(hist):
                c = hist[stage]
                L.append(f"  member {m} stage {stage}  "
                         f"{_bar(c / total)} {c}")
    if s["lanes"]:
        L.append("-- per-lane DAES (Eq. 9 / Eqs. 20-22) --")
        for lane in sorted(s["lanes"]):
            row = s["lanes"][lane]
            L.append(
                f"  lane {lane:>12}  daes {row.get('daes', 0):7.3f}  "
                f"speedup {row.get('speedup', 0):6.2f}x  "
                f"pwr {row.get('power_eff', 0):6.2f}  "
                f"acc {row.get('acc_pct', 0):5.1f}%  "
                f"n={int(row.get('n', 0))}")
    if s["occupancy"]:
        o = s["occupancy"]
        if o.get("slots_total"):
            L.append("-- continuous batching --")
            L.append(f"  slots {_bar(o['slots_in_use'] / o['slots_total'])}"
                     f" {int(o['slots_in_use'])}/{int(o['slots_total'])}")
        if o.get("pages_total"):
            L.append(f"  pages {_bar(o['pages_in_use'] / o['pages_total'])}"
                     f" {int(o['pages_in_use'])}/{int(o['pages_total'])}"
                     f" (peak {int(o.get('pages_peak', 0))})")
    sched = s["scheduler"]
    if sched:
        keys = ("submitted", "completed", "shed", "rejected", "starved")
        L.append("-- scheduler --")
        L.append("  " + "  ".join(f"{k}={sched.get(k, 0)}" for k in keys))
    if s["escalations"]:
        L.append("  escalated: " + "  ".join(
            f"m{m}->{v}" for m, v in sorted(s["escalations"].items())))
    if s["queued"]:
        L.append("  queued: " + "  ".join(
            f"{k}={int(v)}" for k, v in sorted(s["queued"].items())))
    res = s.get("resilience", {})
    health = res.get("engine_health", {})
    if health:
        L.append("-- engine pool --")
        tag = {2: "healthy", 1: "DEGRADED", 0: "DEAD/DRAINED"}
        L.append("  " + "  ".join(
            f"{eng}={tag.get(lvl, lvl)}"
            for eng, lvl in sorted(health.items())))
        L.append(f"  rung={res.get('degradation_rung', 0)}  "
                 f"retries={res.get('retries', 0)}  "
                 f"hedges={res.get('hedges', 0)}  "
                 f"requeues={res.get('requeues', 0)}")
    alarms = []
    if s["recompiles"]:
        alarms.append(f"RECOMPILES={s['recompiles']}")
    if s["errors"]:
        alarms.append("ERRORS=" + ",".join(
            f"{k}:{v}" for k, v in sorted(s["errors"].items())))
    unhealthy = sorted(e for e, lvl in health.items() if lvl < 2)
    if unhealthy:
        alarms.append("UNHEALTHY=" + ",".join(unhealthy))
    if res.get("degradation_rung"):
        alarms.append(f"DEGRADED_RUNG={res['degradation_rung']}")
    n_faults = sum(res.get("faults_injected", {}).values())
    if n_faults:
        alarms.append(f"FAULTS_INJECTED={n_faults}")
    if alarms:
        L.append("!! " + "  ".join(alarms))
    if s["xla_fallbacks"]:
        L.append(f"   xla dispatch decisions: {s['xla_fallbacks']}")
    return "\n".join(L)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--file", help="Prometheus textfile to read")
    src.add_argument("--url", help="metrics endpoint to scrape")
    p.add_argument("--once", action="store_true",
                   help="render one frame and exit")
    p.add_argument("--json", action="store_true",
                   help="emit the parsed summary as JSON")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh period in seconds (live mode)")
    return p


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    while True:
        try:
            fams = scrape(args)
        except Exception as e:                     # noqa: BLE001
            print(f"dartop: scrape failed: {e}", file=sys.stderr)
            return 1
        s = summarize(fams)
        if args.json:
            print(json.dumps(s, indent=2, sort_keys=True))
        else:
            if not args.once:
                print("\x1b[2J\x1b[H", end="")    # clear screen
            print(render(s))
        if args.once:
            return 0
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
