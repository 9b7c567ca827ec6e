"""GST training benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's dataset (cached under ``bench/.cache/``), the
program's driver with weights and batch order from ``--seed``, runs the
driver's first steps (recorded for the reference) and warms every shape,
then the window drives training steps for ``--seconds``.  The driver, the
dataset and the reference are the cell's own modules, found by name
(``harness/spec.py``).  Afterwards the
recorded steps are compared with the plain reference (``correct``).
``--trace 1`` adds a second window, profiled on the device only (at most
``TRACE_SECONDS``), and reports the per-layer metrics instead of the
end-to-end ones; rates come from the first, untraced window.  The last
line of standard output is one JSON object; the numbers compared for
``correct`` come last, on standard error too.  Without a TPU holding as many chips as the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import spec as SPEC  # noqa: E402

TRACE_SECONDS = 10   # the longest window a traced run profiles
# Cores the run's threads share.  Left free to roam, the host's
# host-to-device copies ran about 8% slower in one process out of four on a
# one-chip v5e host; held to four cores, none did.
HOST_CPUS = 4
COMPILES: list = []  # backend compiles seen by this process's listener


def seeds(seed: int):
    """(weight key, batch-order seed, step-rng seed) from ``--seed``."""
    import numpy as np
    w, b, r = np.random.SeedSequence(seed).generate_state(3)
    return int(w) & 0x7FFFFFFF, int(b), int(r) & 0x3FFFFFFF


def _configure_jax(bench: Path):
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(bench / ".cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


class Run:
    """What a per-layer reader sees of one traced run: the traced window's
    trace, steps and program counters (``repro.obs`` registry, counted
    over the window), the rate of the untraced window before it, the
    dataset's ``stats()`` and the configuration's reference module."""

    def __init__(self, cell, driver, data, trace, steps, counters,
                 graphs_per_s):
        self.cell, self.cfg, self.traffic = cell, cell.config, cell.traffic
        self.chips, self.trace, self.steps = cell.chips, trace, steps
        self.counters, self.graphs_per_s = counters, graphs_per_s
        self.h2d_bytes = driver.h2d_bytes
        self.reference = cell.reference
        self._data = data
        kind = driver.devices[0].device_kind
        if kind not in cell.peaks:
            raise SystemExit(f"no peaks for device kind {kind!r} "
                             "in peaks.json")
        self.peak = cell.peaks[kind]

    @property
    def stats(self):
        return self._data.stats()


def bench_mark(x):
    """A one-op program run on the device at each end of the traced window:
    its place in the device's timeline anchors the host's spans."""
    return x + 1


def _window(gen, driver, seconds: float):
    """Steps for ``seconds``; returns (steps, seconds taken, last metrics)."""
    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < seconds:
        m = next(gen)
        steps += 1
    driver.block()
    return steps, time.perf_counter() - t0, m


def _traced_window(jax, gen, driver, seconds: float, mark, keep_trace,
                   name):
    """A window under a device-only profile, between two runs of ``mark``,
    with a fresh program registry; returns (trace, steps, counters)."""
    import shutil
    import tempfile
    import trace_reduce as TR
    from repro.obs.metrics import MetricsRegistry, set_registry
    token = jax.numpy.zeros((), jax.numpy.int32)
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir, profiler_options=_profile_options(jax))
    driver.spans = []
    registry = MetricsRegistry()
    prev = set_registry(registry)
    t_mark = time.perf_counter()
    token = mark(token).block_until_ready()
    steps, window_s, _ = _window(gen, driver, seconds)
    mark(token).block_until_ready()
    set_registry(prev)
    jax.profiler.stop_trace()
    counters = {k: v["value"] for k, v in registry.snapshot().items()
                if v["type"] == "counter"}
    spans = [(s - t_mark, e - t_mark, n) for s, e, n in driver.spans]
    driver.spans = None
    path = next(Path(tdir).rglob("*.xplane.pb"))
    if keep_trace:
        Path(keep_trace).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(keep_trace) / f"{name}.xplane.pb")
        (Path(keep_trace) / f"{name}.spans.json").write_text(
            json.dumps(spans))
    tr = TR.load(str(path), spans, host_window_s=window_s)
    shutil.rmtree(tdir, ignore_errors=True)
    return tr, steps, counters


def run_cell(cell, seed: int, seconds: float, trace: bool,
             keep_trace=None, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    devices = devices[:cell.chips]

    import gc

    import jax.numpy as jnp
    from harness import check
    from harness.proof import PROOF_STEPS
    import trace_reduce as TR

    cfg, traffic = cell.config, cell.traffic
    data = cell.dataset.build(cfg, cell.bench / ".cache" / "data")
    driver = cell.driver.Driver(cfg, traffic, data, devices)
    wkey, bseed, rseed = seeds(seed)
    driver.start(wkey, bseed, rseed)
    gen = driver.steps()
    per_epoch = data.n // traffic["batch_size"]
    for _ in range(max(PROOF_STEPS, per_epoch + 1)):
        next(gen)
    driver.block()
    temp_bytes = driver.lower_temp_bytes()
    if trace:
        mark = jax.jit(bench_mark)
        mark(jnp.zeros((), jnp.int32)).block_until_ready()
    setup_s = time.time() - T_START

    _listen_for_compiles(jax)
    n_before = len(COMPILES)
    steps, window_s, m = _window(gen, driver, seconds)
    graphs_per_s = steps * traffic["batch_size"] / window_s
    if trace:
        tr, traced_steps, counters = _traced_window(
            jax, gen, driver, min(seconds, TRACE_SECONDS), mark, keep_trace,
            cell.name)
    last_loss = float(m["loss"])
    gen.close()
    compiles_in_window = len(COMPILES) - n_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    proof = driver.proof
    driver.close()
    gc.collect()

    ref = cell.reference.run(cfg, traffic, wkey, data.n, data.j_max,
                             proof["batches"], proof["rngs"])
    values = check.numbers(proof, ref)
    finite = bool(jnp.isfinite(last_loss).item())
    correct = check.verdict(values, cell.limits) and finite

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct),
              "attempted": steps + (traced_steps if trace else 0),
              "failed": 0 if finite else 1}
    metrics = {}
    if trace:
        run = Run(cell, driver, data, tr, traced_steps, counters,
                  graphs_per_s)
        for m_ in cell.per_layer:
            value = SPEC.reader(cell.bench, m_["name"])(run)
            if value is not None:
                metrics[m_["name"]] = {"value": value, "unit": m_["unit"]}
        device.update(busy_s=TR.busy_s(tr), window_s=TR.window_s(tr))
        result["breakdown"] = {"device_ops": TR.top_ops(tr),
                               "idle_gaps": TR.idle_gaps(tr)}
    else:
        e2e = {"train_graphs_per_s": graphs_per_s,
               "step_temp_mib": temp_bytes / 2**20, "setup_s": setup_s}
        for m_ in cell.end_to_end:
            metrics[m_["name"]] = {"value": e2e[m_["name"]], "unit": m_["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": values[k], "limit": lim}
                        for k, lim in cell.limits.items()}
    print(f"window: {steps} steps in {window_s:.3f} s"
          + (f", then {traced_steps} traced" if trace else "")
          + f", {compiles_in_window} compiles inside", file=sys.stderr)
    return result


def _listen_for_compiles(jax):
    if getattr(_listen_for_compiles, "on", False):
        return
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: COMPILES.append(ev)
        if "backend_compile" in ev else None)
    _listen_for_compiles.on = True


def _profile_options(jax):
    """Device operations only.  The host tracer, even at level 1, logs the
    runtime's every host-to-device copy and slows this host-bound loop
    about four times; the host's spans are the driver's own instead."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


def main(argv=None) -> int:
    os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:HOST_CPUS])
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb here")
    args = ap.parse_args(argv)
    cell = SPEC.load(args.workload)
    _configure_jax(cell.bench)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
