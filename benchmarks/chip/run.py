"""The chip benchmark: one command for every cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload fit.wiki60m --seed 7 --seconds 10 --trace 0

Everything about a cell is found by name, in files of its own:

* ``BENCHMARK.json`` (checkout root): the cell's configuration, traffic,
  chips and metrics;
* ``configs/<config>.json``: the configuration's sizes;
* ``traffic/<traffic>.json``: the traffic's parameters and the driver
  that runs it (``drivers/<driver>.py``);
* ``cells/<cell>.json``: the limits of the cell's correctness comparison;
* ``metrics/<metric>.py``: one reader per per-layer metric.

The run needs a TPU with at least the cell's chips: without one it exits
non-zero and prints no result. It makes its inputs from ``--seed``, warms
up everything the window runs (set-up), measures for ``--seconds``,
checks what the window's path produced against a plain reference, and
prints the numbers it compared beside their limits as the last lines of
standard error. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the compile cache lives at a fixed place inside the checkout, so only a
# checkout's first run of a cell compiles; the program reads the variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp otherwise
TRACE_ROOT = os.path.join(HERE, ".traces")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` and its files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in reported else [])
    ]
    bench_dir = os.path.join(root, bench["paths"][0])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, conf["file"])),
        traffic=_read_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench_dir, "cells", name + ".json"))["limits"],
        end_to_end=e2e,
        per_layer=per_layer,
    )


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


class Harness:
    """What a driver gets: the cell, the run's arguments, spans, the window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, devices: list):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.trace_dir = os.path.join(TRACE_ROOT, cell.name)
        self.compiles_in_window = 0
        self._counting = False
        self.t_start = T_START

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (free when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def phase(self, name: str) -> None:
        """Marks the end of a part of set-up, on standard error."""
        print(f"setup {name} done at {time.perf_counter() - self.t_start:.3f} s", file=sys.stderr)

    def _on_event(self, event: str, *args, **kwargs) -> None:
        if self._counting and event.endswith("jaxpr_to_mlir_module_duration"):
            self.compiles_in_window += 1

    @contextlib.contextmanager
    def window(self):
        """Wraps the measured window: counts compilations inside it and,
        with ``--trace 1``, records the profiler's trace of it."""
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the harness's spans are enough
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._counting = True
        try:
            with self.span("bench.window"):
                yield
        finally:
            self._counting = False
            if self.trace:
                jax.profiler.stop_trace()

    def memory_peak_bytes(self) -> int:
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices
        ]
        return int(max(peaks))


def _per_layer(cell: Cell, h: Harness, out) -> tuple:
    """Reduce the window's trace and read every per-layer metric."""
    from lib import trace as tr

    red = tr.reduce_dir(h.trace_dir, window_span="bench.window", n_devices=len(h.devices))
    ctx = dict(out.layer)
    ctx.update(trace=red, device_kind=h.devices[0].device_kind)
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"), "metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is None:
            print(f"metric {m['name']}: its reader found nothing to read", file=sys.stderr)
        else:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {
        "device_ops": [[n, s] for n, s in red.top_ops(10)],
        "idle_gaps": [[n, s] for n, s in red.gaps[:10]],
    }
    return metrics, breakdown, red


def main(
    argv: Optional[list] = None,
    *,
    chips: Callable[[int], list] = require_chips,
    find: Callable[[str], Cell] = find_cell,
) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = find(args.workload)
    import jax

    print(f"setup imports done at {time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}; this benchmark runs on the chip only", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(
        f"platform {devices[0].platform} device_kind {devices[0].device_kind} "
        f"device_count {len(devices)}",
        file=sys.stderr,
    )

    h = Harness(cell, args.seed, args.seconds, bool(args.trace), devices)
    h.phase("chips")
    driver = load_module(os.path.join(HERE, "drivers", cell.driver + ".py"), "driver_" + cell.driver)
    out = driver.run(h)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": out.memory_peak_bytes,
    }
    result = {"correct": all(c.ok for c in out.checks) and bool(out.checks)}
    result.update(attempted=out.attempted, failed=out.failed)
    if args.trace:
        metrics, breakdown, red = _per_layer(cell, h, out)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result.update(
            metrics={k: {"value": v, "unit": units[k]} for k, v in out.metrics.items() if k in units},
            device=device,
        )
    result["window_compiles"] = h.compiles_in_window
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in out.checks}

    print(f"window {out.window_s:.3f} s, compilations inside it: {h.compiles_in_window}", file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
