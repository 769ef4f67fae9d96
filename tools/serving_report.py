#!/usr/bin/env python
"""Render per-request waterfalls + the step occupancy timeline from
serving telemetry JSONL (docs/serving.md §observability).

The serving engine (``mxnet_tpu/serving/obs.py``) emits one
``serving.request`` event per lifecycle transition and one
``serving.step_timeline`` event per non-empty step into
``MXNET_TELEMETRY_FILE``. This tool turns that stream into the answer to
"why was request X slow":

* a **per-request waterfall** — one row per request with its phase
  breakdown (queue_wait / prefill / decode / replay / compile_stall, which
  sum to the end-to-end latency), preemption count, SLO verdicts, and a
  proportional phase bar;
* the **occupancy timeline** — per step: batch occupancy, admitted /
  preempted / finished counts, queue depth, KV-pool used/frag;
* the **engine loop** — per step, from the loop's own record on the same
  events (docs/observability.md §The engine loop's record): milliseconds
  in each section (lock, schedule, the group's prefills, the decode
  chunk's build / dispatch / fetch, retire and its counters' part, the
  step before's bookkeeping carried out under this step's dispatch) and the
  two host gaps — fetch's return to next dispatch's return, when the device
  has nothing to run — with their share of the wall clock;
* **totals** — SLO attainment, total replay overhead (what preemptions
  cost), total compile stall (what cold buckets cost).

Usage::

    MXNET_TELEMETRY_FILE=/tmp/serving.jsonl python tools/serve.py ... &
    python tools/serving_report.py /tmp/serving.jsonl
    python tools/serving_report.py --json /tmp/serving.jsonl   # machine use

``--json`` prints one JSON object ({"requests", "steps", "slo"}) for
scripting; the e2e test asserts attribution closure through it. The
chrome-trace view of the same stream is
``tools/trace_merge.py --serving-lanes``.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from trace_merge import request_segments  # noqa: E402  (shared walker)

PHASES = ("queue_wait", "prefill", "decode", "replay", "compile_stall")
_BAR_CHARS = {"queue_wait": "q", "prefill": "P", "decode": "D",
              "replay": "R", "compile_stall": "C"}


def load_events(path):
    """Parse a telemetry JSONL file into (request_events, step_events)."""
    requests, steps = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue   # torn tail of a killed server: keep the rest
            if rec.get("type") != "event":
                continue
            name = rec.get("event")
            if name == "serving.request" and "request_id" in rec:
                requests.append(rec)
            elif name == "serving.step_timeline":
                steps.append(rec)
    return requests, steps


def summarize_requests(events):
    """One summary dict per request (submission order): identity, phase
    breakdown from the terminal event (exact — the engine's clock), the
    segment walk (for bars/lanes), SLO verdicts, preemptions."""
    by_req = {}
    for rec in events:
        key = (str(rec.get("engine", "")), str(rec["request_id"]))
        by_req.setdefault(key, []).append(rec)
    out = []
    for key in sorted(by_req, key=lambda k: float(by_req[k][0]["ts"])):
        engine, request_id = key
        evs = sorted(by_req[key], key=lambda r: float(r["ts"]))
        terminal = next((r for r in evs
                         if r.get("state") in ("finished", "failed")), None)
        phases = dict.fromkeys(PHASES, 0.0)
        if terminal is not None and "phases" in terminal:
            phases.update(terminal["phases"])
        out.append({
            "request_id": request_id,
            "engine": engine,
            "state": terminal["state"] if terminal else "in-flight",
            "submitted_ts": float(evs[0]["ts"]),
            "e2e_s": terminal.get("e2e_s") if terminal else None,
            "phases": phases,
            "phase_sum_s": round(sum(phases.values()), 6),
            "tokens": terminal.get("tokens") if terminal else None,
            # speculative decoding's draft/verify split INSIDE the decode
            # phase (sub-attribution — not part of the phase sum, which
            # stays an exact partition over PHASES)
            "spec_draft_s": terminal.get("spec_draft_s", 0.0)
            if terminal else 0.0,
            "spec_verify_s": terminal.get("spec_verify_s", 0.0)
            if terminal else 0.0,
            "preemptions": max([r.get("preemptions", 0) for r in evs]
                               or [0]),
            "slo_ttft_ok": terminal.get("slo_ttft_ok") if terminal else None,
            "slo_tpot_ok": terminal.get("slo_tpot_ok") if terminal else None,
            "segments": request_segments(evs),
        })
    return out


def _bar(summary, width=32):
    """Proportional phase bar over the request's end-to-end span. Stalls
    are debited from their enclosing phase in the attribution, so the bar
    draws the SEGMENT timeline (what the request was doing when) and
    flags stall time in the breakdown columns instead."""
    segs = [(p, s, e) for p, s, e in summary["segments"] if e is not None]
    if not segs:
        return "-" * width
    t0 = segs[0][1]
    t1 = max(e for _p, _s, e in segs)
    span = max(t1 - t0, 1e-9)
    bar = []
    for i in range(width):
        t = t0 + (i + 0.5) / width * span
        ch = "."
        for phase, s, e in segs:
            if s <= t < e:
                ch = _BAR_CHARS.get(phase, "?")
                break
        bar.append(ch)
    return "".join(bar)


def _slo_cell(summary):
    verdicts = [summary["slo_ttft_ok"], summary["slo_tpot_ok"]]
    if all(v is None for v in verdicts):
        return "--"
    return "ok" if all(v in (True, None) for v in verdicts) else "MISS"


def render(requests, steps, bar_width=32, file=sys.stdout):
    """The human report: waterfall table, totals, occupancy timeline."""
    w = file.write
    w("serving_report: %d requests, %d timeline steps\n\n"
      % (len(requests), len(steps)))
    if requests:
        w("per-request waterfall (seconds; q=queue P=prefill D=decode "
          "R=replay; stall debited from its phase):\n")
        w("%-16s %9s %9s %9s %9s %9s %9s %4s %5s %4s  %s\n"
          % ("request", "e2e", "queue", "prefill", "decode", "replay",
             "stall", "pre", "slo", "tok", "timeline"))
        for s in requests:
            ph = s["phases"]
            w("%-16s %9s %9.3f %9.3f %9.3f %9.3f %9.3f %4d %5s %4s  %s\n"
              % (s["request_id"],
                 ("%9.3f" % s["e2e_s"]) if s["e2e_s"] is not None else "--",
                 ph["queue_wait"], ph["prefill"], ph["decode"], ph["replay"],
                 ph["compile_stall"], s["preemptions"], _slo_cell(s),
                 s["tokens"] if s["tokens"] is not None else "--",
                 _bar(s, bar_width)))
        spec = [s for s in requests
                if s["spec_draft_s"] or s["spec_verify_s"]]
        if spec:
            w("\nspeculative decode split (inside the decode column; "
              "other = decode - draft - verify):\n")
            w("%-16s %9s %9s %9s %9s\n"
              % ("request", "decode", "draft", "verify", "other"))
            for s in spec:
                dec = s["phases"]["decode"]
                w("%-16s %9.3f %9.3f %9.3f %9.3f\n"
                  % (s["request_id"], dec, s["spec_draft_s"],
                     s["spec_verify_s"],
                     dec - s["spec_draft_s"] - s["spec_verify_s"]))
        done = [s for s in requests if s["state"] == "finished"]
        judged = [s for s in done if s["slo_ttft_ok"] is not None]
        good = sum(1 for s in judged
                   if s["slo_ttft_ok"] and s["slo_tpot_ok"] in (True, None))
        w("\ntotals: %d finished, %d failed/in-flight | replay overhead "
          "%.3fs | compile stall %.3fs | preemptions %d"
          % (len(done), len(requests) - len(done),
             sum(s["phases"]["replay"] for s in requests),
             sum(s["phases"]["compile_stall"] for s in requests),
             sum(s["preemptions"] for s in requests)))
        if judged:
            w(" | SLO %d/%d (%.0f%%)"
              % (good, len(judged), 100.0 * good / len(judged)))
        w("\n")
    if steps:
        w("\noccupancy timeline (per engine step):\n")
        w("%6s %4s %4s %4s %4s %6s %8s %6s\n"
          % ("step", "occ", "adm", "pre", "fin", "queue", "kv_used",
             "frag"))
        for rec in sorted(steps, key=lambda r: (str(r.get("engine", "")),
                                                r.get("step", 0))):
            w("%6s %4d %4d %4d %4d %6d %8d %6d\n"
              % (rec.get("step", "?"), rec.get("occupancy", 0),
                 rec.get("admitted", 0), rec.get("preempted", 0),
                 rec.get("finished", 0), rec.get("queue", 0),
                 rec.get("kv_used", 0), rec.get("kv_frag_slots", 0)))
        render_loop(steps, file)


#: the loop table's columns: header, the record's fields summed into it
_LOOP_COLUMNS = (
    ("lock", ("lock_s",)), ("sched", ("schedule_s",)),
    ("prefill", ("prefill_build_s", "prefill_dispatch_s", "prefill_fetch_s",
                 "prefill_retire_s")),
    ("build", ("decode_build_s",)), ("disp", ("decode_dispatch_s",)),
    ("fetch", ("decode_fetch_s",)), ("retire", ("retire_s",)),
    ("counters", ("retire_counters_s",)), ("deferred", ("deferred_s",)),
    ("gap_chunk", ("gap_chunk_s",)), ("gap_group", ("gap_group_s",)))


def loop_rows(steps):
    """(engine, step, prompts, chunk steps, {column: ms or None}) for each
    step event that carries the loop's record (a program from before it
    sends none), in engine and step order."""
    rows = []
    for rec in sorted(steps, key=lambda r: (str(r.get("engine", "")),
                                            r.get("step", 0))):
        if "gap_chunk_s" not in rec:
            continue
        ms = {}
        for col, fields in _LOOP_COLUMNS:
            vals = [rec.get(f) for f in fields]
            ms[col] = (None if all(v is None for v in vals)
                       else 1e3 * sum(v or 0.0 for v in vals))
        rows.append((str(rec.get("engine", "")), rec.get("step", 0),
                     rec.get("prefills", 0), rec.get("chunk_steps", 0), ms))
    return rows


def render_loop(steps, file=sys.stdout):
    """The engine loop's table and its one line of totals."""
    rows = loop_rows(steps)
    if not rows:
        return
    w = file.write
    w("\nengine loop (per step, milliseconds; a gap runs from a blocking "
      "fetch's return to the next dispatch's return, on the host's "
      "clock):\n")
    w("%6s %3s %3s " % ("step", "pr", "n")
      + " ".join("%9s" % col for col, _f in _LOOP_COLUMNS) + "\n")
    for _engine, step, prompts, n, ms in rows:
        w("%6s %3d %3d " % (step, prompts, n)
          + " ".join("%9s" % ("--" if ms[col] is None else "%.3f" % ms[col])
                     for col, _f in _LOOP_COLUMNS) + "\n")
    gaps = sum((ms["gap_chunk"] or 0.0) + (ms["gap_group"] or 0.0)
               for *_r, ms in rows)
    chunks = sum(1 for _e, _s, _p, n, _ms in rows if n)
    wall = (max(float(r["ts"]) for r in steps)
            - min(float(r["ts"]) for r in steps)) if len(steps) > 1 else 0.0
    w("loop totals: %d steps, %d chunks | host gaps %.3f ms"
      % (len(rows), chunks, gaps))
    if wall > 0:
        w(" = %.1f%% of the %.3f s from the first step's event to the "
          "last's" % (100.0 * gaps / 1e3 / wall, wall))
    if chunks:
        w(" | retire %.3f ms a chunk, its counters %.3f"
          % (sum(ms["retire"] or 0.0 for *_r, ms in rows) / chunks,
             sum(ms["counters"] or 0.0 for *_r, ms in rows) / chunks))
    w("\n")


def report(path):
    """Machine form: {"requests": [...], "steps": [...], "slo": {...}}."""
    events, steps = load_events(path)
    requests = summarize_requests(events)
    judged = [s for s in requests if s["slo_ttft_ok"] is not None]
    good = sum(1 for s in judged
               if s["slo_ttft_ok"] and s["slo_tpot_ok"] in (True, None))
    return {
        "requests": requests,
        "steps": steps,
        "slo": {"judged": len(judged), "good": good,
                "attainment": (good / len(judged)) if judged else None},
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="per-request serving waterfalls + occupancy timeline "
                    "from telemetry JSONL")
    ap.add_argument("input", help="telemetry JSONL file "
                                  "(MXNET_TELEMETRY_FILE sink)")
    ap.add_argument("--json", action="store_true",
                    help="print one machine-readable JSON object instead "
                         "of the tables")
    ap.add_argument("--bar-width", type=int, default=32,
                    help="timeline bar width in characters")
    args = ap.parse_args(argv)
    if args.json:
        rep = report(args.input)
        for s in rep["requests"]:
            s.pop("segments", None)   # ts tuples: noise for machine use
        print(json.dumps(rep))
        return 0
    events, steps = load_events(args.input)
    render(summarize_requests(events), steps, bar_width=args.bar_width)
    return 0


if __name__ == "__main__":
    sys.exit(main())
