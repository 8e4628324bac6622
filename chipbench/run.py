"""Run one cell of the benchmark on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Serves the cell's traffic through the program's ``ServingEngine`` (PREMA,
Algorithm-3 mechanism choice, one batch slot) for ``--seconds`` of wall
time, and waits past the close for the open-loop requests it sent, timed
from the client's side (``harness/serve.py``), then checks the
served tokens against the configuration's float32 reference
(``harness/check.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics from a
profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``, each compared number beside its limit.
The same numbers end standard error.

It refuses, with a non-zero exit and no result, any platform but a TPU and
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reader reads (``chipbench/metrics``)."""
    window: object
    spec: Dict
    cell: Dict
    mix: Dict
    peaks: Dict
    n_chips: int
    hi_priority: int
    compiles: List[Tuple[float, str]]
    trace: Optional[object] = None

    def due_in_window(self, priority: int):
        from chipbench.harness.serve import due_in_window
        return due_in_window(self.window, priority)

    @property
    def prompt_len(self) -> Dict[int, int]:
        return {rid: r.req.prompt_len for rid, r in self.window.records.items()}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    a fixed directory inside the checkout; every program is kept, however
    quickly it compiled, so that a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int):
    """The devices of this process, refusing anything but ``n`` or more
    TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX has {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def warm_up(model, params, shapes) -> None:
    """Compile (or load from the cache) every program the window will run,
    by serving each prompt length the traffic sends, once, alone, to the
    longest output sent after it: embedding, prefill periods, head and
    every size the cache grows to while it decodes."""
    import numpy as np
    from repro.serving import PreemptibleExecutor
    ex = PreemptibleExecutor(model, params)
    for plen, n in shapes:
        st = ex.run_uninterrupted({"tokens": np.zeros((1, plen), np.int32)},
                                  max_new_tokens=n)
        np.asarray(st.tokens_out[-1])


def layer_metrics(root: Path, cell, data: RunData) -> Dict[str, Dict]:
    """Each per-layer metric of the cell that its reader finds something
    to read for, by the reader in ``chipbench/metrics/<name>.py``."""
    from chipbench.harness import catalog
    out = {}
    for m in cell.per_layer:
        v = catalog.reader(root, m["name"])(data)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def trace_dir(root: Path, workload: str) -> Path:
    return root / "chipbench" / ".runs" / f"trace-{workload}"


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             peaks: Optional[Dict] = None,
             log: Callable[[str], None] = _log) -> Dict:
    """One run of a cell; returns the result object that ``main`` prints.
    Tests drive it on the CPU with ``require_tpu=False`` and peaks of
    their own."""
    import jax
    from chipbench.harness import catalog, check, model as model_mod
    from chipbench.harness import peaks as peaks_mod
    from chipbench.harness import serve, stats
    from chipbench.harness import trace as trace_mod
    from chipbench.harness.traffic import Traffic

    cell = catalog.find(root, workload)
    devs = chips(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    if peaks is None:
        peaks = peaks_mod.for_kind(dev.device_kind)
    compiles: List[Tuple[float, str]] = []

    def on_event(event, secs, **kw):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            compiles.append((time.perf_counter(), event))
    jax.monitoring.register_event_duration_secs_listener(on_event)

    spec = cell.spec
    model, cfg = model_mod.build_model(cell.config_name, spec)
    model_mod.check_layout(model, spec)
    t0 = time.perf_counter()
    params = model_mod.make_weights(spec, seed)
    t_weights = time.perf_counter() - t0
    traffic = Traffic(cell.mix, cell.params, cfg.vocab_size, seed, seconds)
    t0 = time.perf_counter()
    shapes = traffic.shapes()
    warm_up(model, params, shapes)
    log(f"set-up: weights {t_weights:.3f} s, warm-up of {len(shapes)} prompt "
        f"lengths {time.perf_counter() - t0:.3f} s, {len(compiles)} programs "
        f"compiled or loaded")

    tdir = trace_dir(root, workload)
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    window = serve.serve(cell, model, params, traffic, seconds,
                         annotate=trace)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        path = glob.glob(str(tdir / "**" / "*.xplane.pb"), recursive=True)[0]
        reduced = trace_mod.reduce(trace_mod.load(path), seconds)
        shutil.rmtree(tdir, ignore_errors=True)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    hi_prio = max(int(t["priority"]) for t in cell.mix["tenants"])
    data = RunData(window=window, spec=spec, cell=cell.params, mix=cell.mix,
                   peaks=peaks,
                   n_chips=cell.chips, hi_priority=hi_prio, compiles=compiles,
                   trace=reduced)
    e2e = stats.end_to_end(window, cell.params, cell.mix)
    e2e["setup_s"] = setup_s
    metrics = (layer_metrics(root, cell, data) if trace else
               {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end if e2e.get(m["name"]) is not None})
    sent = [r for r in window.records.values() if r.sent < window.end]
    failed = [r for r in sent if r.tokens is not None
              and r.tokens.shape[1] != r.req.max_new_tokens]
    log(f"window: {len(sent)} requests sent, {sum(r.complete is not None for r in sent)} "
        f"completed, {e2e['n_hi']} interactive ({e2e['n_hi_censored']} censored), "
        f"{sum(r.preempts for r in window.records.values())} preemptions, "
        f"{sum(window.start <= t <= window.end for t, _ in compiles)} compiles, "
        f"open-loop requests done {window.drain_end - window.end:.3f} s "
        f"past the close")

    # the reference runs after the window, with the engine's state freed
    records = list(window.records.values())
    gc.collect()
    t0 = time.perf_counter()
    limits = cell.params["correct"]
    recs = check.sample(records, seed, limits["sample_tokens"],
                        limits["sample_requests"])
    gap = check.widest_gap(spec, params, recs) if recs else None
    n_tok = sum(r.tokens.shape[1] for r in recs)
    log(f"reference: {len(recs)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t0:.2f} s")
    checks = {"max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]}}
    correct = gap is not None and not failed and gap <= limits["max_logit_gap"]

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {"correct": correct, "attempted": len(sent), "failed": len(failed),
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": trace_mod.device_time_by_program(reduced),
            "idle_gaps": trace_mod.gaps_by_host(reduced)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_compile_cache(ROOT)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        _log(f"run: {e}")
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
