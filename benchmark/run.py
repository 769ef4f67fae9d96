#!/usr/bin/env python3
"""One cell, once, in this process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Checks the device FIRST (no TPU, fewer chips than the cell asks for, or a
``device_kind`` the peaks table does not know: non-zero exit, no result
line), loads, warms up the cell's own shapes, measures for ``--seconds``,
checks the outputs, and prints as the LAST line of stdout one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Everything else worth seeing goes on earlier lines (one JSON object each)
or into ``chiprun_out/benchmark/<cell>/``.

``--rehearsal`` takes the cell from ``benchmark/rehearsal/<name>.json``
instead, a manifest of its own (tiny sizes, any platform). A rehearsal
never prints ``metrics`` or ``device``: nothing it sees can be mistaken for
a device number.

The harness is driven by data; README.md beside this file says which files
a new cell needs.
"""
import time

T_START = time.time()   # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import peaks as peaks_table  # noqa: E402


def say(what, **fields):
    """An earlier line: one JSON object, never the last."""
    print(json.dumps(dict(bench=what, **fields)), flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit("no workload %r in the manifest (has: %s)"
                     % (name, [c["name"] for c in manifest["workloads"]]))


def resolve(manifest, cell):
    """The cell's files, found by name: its configuration's JSON (named in
    the manifest) with the module beside it (or the one its ``"module"``
    key shares), its traffic mix, the driver of the mix's kind."""
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    cfg_path = os.path.join(ROOT, cfg_entry["file"])
    config = load_json(cfg_path)
    mod_path = (os.path.join(os.path.dirname(cfg_path), config["module"])
                if "module" in config else cfg_path[:-len(".json")] + ".py")
    mix_path = os.path.join(ROOT, manifest.get("traffic_dir",
                                               "benchmark/traffic"),
                            cell["traffic"] + ".json")
    mix = load_json(mix_path)
    driver = importlib.import_module("benchmark.drivers." + mix["driver"])
    return (config, load_module(mod_path, "bench_config"), mix, mix_path,
            driver)


def metrics_for(manifest, cell_name, group):
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def read_layer_metrics(manifest, cell_name, obs, reported):
    """Each per-layer metric has a reader of its own,
    ``layer_metrics/<name>.py`` with ``read(obs)``. A reader that finds
    nothing to read returns None and the metric is left out; so is one
    whose end-to-end metric this cell does not report."""
    out = {}
    for m in metrics_for(manifest, cell_name, "per_layer"):
        if m["moves"] not in reported:
            continue
        value = importlib.import_module(
            "benchmark.layer_metrics." + m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_device(cell, rehearsal):
    """The device as jax reports it. Outside a rehearsal: a TPU, at least
    the chips the cell asks for, and a kind the peaks table knows."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if rehearsal:
        return devices, device, None
    if device["platform"] != "tpu":
        raise SystemExit("the benchmark needs a TPU; jax.devices() = %s"
                         % (devices,))
    if len(devices) < cell["chips"]:
        raise SystemExit("cell %s needs %d chips, jax sees %d"
                         % (cell["name"], cell["chips"], len(devices)))
    return devices, device, peaks_table.peaks_for(device["kind"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    manifest_path = (
        os.path.join(HERE, "rehearsal", args.workload + ".json")
        if args.rehearsal else os.path.join(ROOT, "BENCHMARK.json"))
    manifest = load_json(manifest_path)
    cell = find_cell(manifest, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else manifest["run_seconds"]
    config, config_mod, mix, mix_path, driver = resolve(manifest, cell)

    devices, device, peak = check_device(cell, args.rehearsal)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", cell["name"],
                           "seed%d-trace%d" % (args.seed, args.trace))
    os.makedirs(out_dir, exist_ok=True)
    say("start", cell=cell["name"], seed=args.seed, seconds=seconds,
        trace=args.trace, device=device, rehearsal=args.rehearsal)

    from benchmark import harness

    ctx = types.SimpleNamespace(
        cell=cell, config=config, config_mod=config_mod, mix=mix,
        mix_path=mix_path, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), devices=devices[:cell["chips"]],
        chips=cell["chips"],
        platform=device["platform"], peak=peak, out_dir=out_dir,
        t_start=T_START, say=say)
    harness.enable_compile_cache(ctx)
    result = driver.run(ctx)   # {"correct","attempted","failed","e2e","obs"}

    device["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.devices)
    say("memory", runtime=ctx.devices[0].memory_stats())
    e2e_names = [m["name"] for m in metrics_for(manifest, cell["name"],
                                                "end_to_end")]
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    missing = [n for n in e2e_names if n not in result["e2e"]]
    if missing:
        raise SystemExit("the driver did not measure %s" % missing)
    say("end_to_end", **{n: result["e2e"][n] for n in e2e_names})
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        obs = result["obs"]
        obs["memory_peak_bytes"] = device["memory_peak_bytes"]
        metrics = read_layer_metrics(manifest, cell["name"], obs,
                                     set(e2e_names))
        trace = obs.get("trace")
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in trace["device_ops"]],
                "idle_gaps": [[n, s] for n, s in trace["idle_gaps"]]}
    else:
        metrics = {n: {"value": float(result["e2e"][n]), "unit": units[n]}
                   for n in e2e_names}
    say("cache", **harness.cache_bytes())
    if args.rehearsal:
        # never "metrics" or "device": no number of a rehearsal can be
        # taken for a result
        line.update(rehearsal=True, observed=metrics,
                    platform=device["platform"])
    else:
        line.update(metrics=metrics, device=device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
