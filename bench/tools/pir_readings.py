"""One benchmark run, as ``bench/run.py`` makes it, that also reads the
program's own ``pir.*`` spans and wait counters: the readings PERF.md
gives for the served path's layers until the benchmark's metrics read
them.

    python3 bench/tools/pir_readings.py --workload <cell> --seed <n> \\
        [--seconds 50] [--trace 1] [--root DIR] [--tiny]

Prints run.py's result line, then one line ``PIR {json}``:

* ``answer_host_gap_ms``: the mean over ``pir.answer`` spans wholly inside the
  window of (span time less device time inside it);
* ``finalize_ms``, ``mean_ms[<span>]``, ``count[<span>]``,
  ``total_s[<span>]``: span durations, counts and covered seconds;
* ``idle_s_in[<span>]``: device-idle seconds inside a span's cover;
* ``admit_wait_ms``, ``queue_wait_ms``, ``dispatch_wait_ms`` (the
  readers of ``bench/metrics/<name>.poisson.py`` beside this tool), and
  ``execute_ms``: the window's wait counters per request, beside
  ``mean_latency_ms`` and ``mean_lateness_ms`` from the traffic generator;
* ``idle_by_label_*``: the window's device-idle seconds by the innermost
  covering span at each gap's middle (``_all``: among ``bench.*`` and
  ``pir.*``; ``_pir``: ``pir.*`` alone; a ``pir.wait.*`` span only where
  nothing else covers the gap; else ``host``), and ``_bench``: the
  benchmark's own labels;
* ``end_to_end_traced``: the end-to-end readers on a traced run, which
  run.py reports only untraced.

It wraps ``harness.Session.counters`` (for a checkout whose harness does
not read the wait counters), ``xplane.load`` and ``harness.load_reader``
in its own process to see what run.py throws away; the benchmark's files
are not changed. ``--root`` runs the checkout at DIR (say, a parent
commit unpacked beside this one); on a program without the counters
their readings are left out. ``--tiny`` runs a CPU-sized cell for
rehearsal."""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()

#: the program's wait counters (``ServingPipeline`` and ``AsyncFrontend``)
COUNTERS = ("admit_wait_s", "admit_count", "queue_wait_s", "dequeued",
            "dispatch_wait_s", "execute_s")
#: spans summarised one by one
NAMED = ("pir.plan", "pir.query_gen", "pir.execute", "pir.answer",
         "pir.answer.server", "pir.finalize", "pir.reconstruct",
         "pir.unpack", "pir.cache_insert", "pir.admit", "pir.wait.inflight",
         "pir.wait.arrivals", "pir.idle.prefill", "pir.idle.autotune")
IDLE_IN = ("pir.answer.server", "pir.answer", "pir.finalize", "pir.plan")
#: the wait readers of this checkout's ``bench/metrics/``, by the key printed
WAIT_READERS = {"admit_wait_ms": "admit_wait_ms.poisson",
                "queue_wait_ms": "queue_wait_ms.poisson",
                "dispatch_wait_ms": "dispatch_wait_ms.poisson"}
METRICS = Path(__file__).resolve().parents[1] / "metrics"


def own_reader(name):
    """``read`` of ``bench/metrics/<name>.py`` beside this tool, whatever
    checkout ``--root`` runs."""
    spec = importlib.util.spec_from_file_location(
        f"pir_readings_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def label_sweep(spans, gaps, window_span="bench.window", skip_bench=False):
    """Each gap ``(a, b)`` labelled by the innermost span covering its
    middle: among spans other than the window (and other than
    ``bench.*`` with ``skip_bench``) that are not ``pir.wait.*`` first,
    then ``pir.wait.*``, else ``host``. One sweep over gaps and spans
    sorted by start. Returns ``(a, b, label)`` in the gaps' order."""
    cand = [s for s in spans if s.name != window_span
            and not (skip_bench and s.name.startswith("bench."))]
    order = sorted(range(len(cand)), key=lambda i: cand[i].start_ns)
    active, k, out = [], 0, []
    for a, b in sorted(gaps):
        t = (a + b) / 2
        while k < len(order) and cand[order[k]].start_ns <= t:
            active.append(order[k])
            k += 1
        active = [i for i in active if cand[i].end_ns > t]
        work = [i for i in active if not cand[i].name.startswith("pir.wait.")]
        pick = work or active
        if pick:
            best = min(pick, key=lambda i: (cand[i].end_ns - cand[i].start_ns, i))
            out.append((a, b, cand[best].name))
        else:
            out.append((a, b, "host"))
    return out


def by_label(gaps):
    d = {}
    for a, b, lab in gaps:
        d[lab] = d.get(lab, 0.0) + (b - a) / 1e9
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def program_spans(profile, window, span_cls):
    """Every ``pir.*`` host span that overlaps ``window``."""
    lo, hi = window
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("pir."):
                    a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                    if b > lo and a < hi:
                        out.append(span_cls(e.name, a, b, dict(e.stats)))
    return out


def window_waits(run):
    """The window's counter deltas and the wait counters per request, in
    ms, with the traffic generator's mean latency and lateness beside them."""
    c0, c1 = run["counters_open"], run["counters_close"]
    dc = {k: c1[k] - c0[k] for k in c1 if k in c0}
    out = {"window_counters": dc}
    for key, name in WAIT_READERS.items():
        out[key] = own_reader(name)(run)
    out["execute_ms"] = dc["execute_s"] / dc["dequeued"] * 1e3 \
        if dc.get("dequeued") and "execute_s" in dc else None
    done = [lk for lk in run["lookups"] if not math.isnan(lk.t_done)]
    if done:
        out["mean_latency_ms"] = sum(lk.t_done - lk.t_sched for lk in done) / len(done) * 1e3
        out["mean_lateness_ms"] = sum(lk.t_submit - lk.t_sched for lk in done) / len(done) * 1e3
        parts = [out[k] for k in ("admit_wait_ms", "queue_wait_ms",
                                  "dispatch_wait_ms", "execute_ms")]
        if all(p is not None for p in parts):
            out["sum_parts_ms"] = sum(parts)
            out["latency_less_lateness_ms"] = (out["mean_latency_ms"]
                                               - out["mean_lateness_ms"])
    return out


def span_summary(red, prog, xplane):
    """The span readings of one traced window (see the module's doc)."""
    lo, hi = red.window
    named = {}
    for s in prog:
        named.setdefault(s.name, []).append(s)

    def cover(name):
        return xplane.clip(xplane.merge(
            [(s.start_ns, s.end_ns) for s in named.get(name, [])]), lo, hi)

    def mean_ms(name):
        ss = named.get(name, [])
        return sum(s.end_ns - s.start_ns for s in ss) / len(ss) / 1e6 if ss else None

    def idle_in(name):
        c = cover(name)
        busy = sum(xplane.overlap(dev, c) for dev in red.busy) / max(1, len(red.busy))
        return (xplane.measure(c) - busy) / 1e9

    whole = [s for s in named.get("pir.answer", [])
             if s.start_ns >= lo and s.end_ns <= hi]
    out = {"answer_host_gap_ms": None, "finalize_ms": mean_ms("pir.finalize")}
    if whole:
        c = xplane.merge([(s.start_ns, s.end_ns) for s in whole])
        busy = sum(xplane.overlap(dev, c) for dev in red.busy) / max(1, len(red.busy))
        out["answer_host_gap_ms"] = (xplane.measure(c) - busy) / len(whole) / 1e6
    for nm in NAMED:
        out[f"mean_ms[{nm}]"] = mean_ms(nm)
        out[f"count[{nm}]"] = len(named.get(nm, []))
        out[f"total_s[{nm}]"] = xplane.measure(cover(nm)) / 1e9
    for nm in IDLE_IN:
        out[f"idle_s_in[{nm}]"] = idle_in(nm)
    gaps = [(a, b) for a, b, _ in red.gaps]
    idle_total = sum(b - a for a, b in gaps) / 1e9
    lab_all = label_sweep(red.spans + prog, gaps, xplane.WINDOW_SPAN)
    lab_pir = label_sweep(prog, gaps, xplane.WINDOW_SPAN, skip_bench=True)
    out["idle_total_s"] = idle_total
    out["idle_by_label_all"] = by_label(lab_all)
    out["idle_by_label_pir"] = by_label(lab_pir)
    out["idle_by_label_bench"] = xplane.idle_by_label(red)
    if idle_total:
        out["pir_covered_share"] = sum(
            b - a for a, b, lab in lab_pir if lab != "host") / 1e9 / idle_total
        out["pir_share_all"] = sum(
            b - a for a, b, lab in lab_all if lab.startswith("pir.")) / 1e9 / idle_total
    out["longest_gaps_pir"] = [[lab, (b - a) / 1e9] for a, b, lab in
                               sorted(lab_pir, key=lambda g: g[0] - g[1])[:10]]
    return out


def install(harness, xplane, capture, tiny):
    """Wrap the harness so that a run keeps its trace's ``pir.*`` spans,
    the program's wait counters and the run dict in ``capture``."""
    counters = harness.Session.counters

    def with_waits(self):
        out = counters(self)
        m = self.fe.metrics
        out.update({k: m[k] for k in COUNTERS if k in m})
        return out

    harness.Session.counters = with_waits

    def reduce(profile):
        try:
            return xplane.reduce_profile(profile)
        except ValueError:
            if not tiny:
                raise
        # a CPU rehearsal's trace has no device plane: all window idle
        spans = xplane._host_spans(profile)
        w = next(s for s in spans if s.name == xplane.WINDOW_SPAN)
        return xplane.Reduction(
            window=(w.start_ns, w.end_ns), devices=1, busy_ns=0.0, op_ns={},
            busy=[[]], gaps=[(w.start_ns, w.end_ns, "host")],
            spans=[s for s in spans if s.end_ns > w.start_ns
                   and s.start_ns < w.end_ns],
            clock_offsets_ns=[0.0])

    def load(path):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(path)
        red = reduce(profile)
        capture["red"] = red
        capture["prog"] = program_spans(profile, red.window, xplane.Span)
        return red

    xplane.load = load
    load_reader = harness.load_reader

    def keeping_run(name):
        fn = load_reader(name)

        def read(run):
            capture["run"] = run
            return fn(run)

        return read

    harness.load_reader = keeping_run
    return load_reader


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50)
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--root", default=".")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root)
    sys.path.insert(0, os.path.join(root, "bench"))
    sys.path.insert(0, os.path.join(root, "src"))
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.path.insert(0, os.path.join(root, "bench", "tests"))
    import harness
    import xplane

    capture = {}
    read_metric = install(harness, xplane, capture, args.tiny)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if args.tiny:
        import test_correct

        compiles = harness.CompileLog()
        spec = test_correct.tiny_spec(args.workload)
    else:
        chips = {w["name"]: int(w["chips"]) for w in bench["workloads"]}
        compiles, _, devices = harness.start(chips[args.workload])
        print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
        spec = None
    result = harness.run_cell(
        bench, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, compiles=compiles, spec=spec,
        log=lambda msg: print(msg, flush=True))
    print(json.dumps(result), flush=True)
    run = capture.get("run")
    summary = {}
    if run is not None:
        summary.update(window_waits(run))
        if args.trace:
            summary.update(span_summary(capture["red"], capture["prog"], xplane))
            summary["end_to_end_traced"] = {
                m["name"]: read_metric(m["name"])(run)
                for m in harness.cell_spec(bench, args.workload)["end_to_end"]}
    print("PIR " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    try:
        main()
        code = 0
    except Exception:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # as run.py: leave without waiting on the program's worker threads
    os._exit(code)
