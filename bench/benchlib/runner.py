"""One run of one cell: weights and requests from the seed, the served
path through its warm-up, ramp and measured window, the per-layer
readers in a traced run, and the comparison with the reference."""

from __future__ import annotations

import dataclasses
import importlib
import shutil
import time

import jax

from benchlib import check, serve, steps, trace, traffic, weights, window
from benchlib.costs import Model
from benchlib.spec import ROOT, SpecError

TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 8      # a traced run profiles the window's last seconds


class ChipMissing(SpecError):
    """No accelerator of the kind the cell needs, or kernels that would
    run in interpret mode."""


def require_chip(chips: int):
    from repro.kernels.common import resolve_interpret
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise ChipMissing(f"JAX's first device is {devs[0].platform!r}, "
                          f"not a TPU")
    if len(devs) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, JAX has "
                          f"{len(devs)}")
    if resolve_interpret():
        raise ChipMissing("Pallas kernels would run in interpret mode "
                          "(is ITA_PALLAS_INTERPRET set?)")


@dataclasses.dataclass
class RunData:
    """What the per-layer readers read (``metrics/<name>.py``)."""
    window: window.Window
    slots: int
    model: Model
    peaks: dict
    trace: trace.Trace | None = None
    trace_window_s: float | None = None
    steps: list | None = None


class Tracer:
    def __init__(self, recorder):
        self.recorder = recorder

    def start(self):
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.recorder.active = True

    def stop(self):
        self.recorder.active = False
        jax.profiler.stop_trace()


def device_info(chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        peaks: dict, control: bool = False) -> dict:
    conf, mix = cell.config, cell.traffic
    geo = mix["geometry"]
    cfg = serve.program_config(conf)
    params = weights.make(serve.param_layout(cfg), conf, seed)
    jax.block_until_ready(params)
    requests = traffic.warmup_requests(mix) \
        + traffic.generate(mix, conf["vocab_size"], seed)

    compiles = serve.CompileCounter()
    recorder = serve.SegmentRecorder()
    tracer = Tracer(recorder) if traced else None
    clock = serve.WindowClock(
        traffic.warmup_steps(mix) + int(mix["ramp_steps"]), seconds,
        trace_seconds=TRACE_SECONDS if traced else None,
        on_trace_start=tracer.start if traced else None,
        on_trace_stop=tracer.stop if traced else None, compiles=compiles)
    uninstall = recorder.install(geo["slots"]) if traced \
        else (lambda: None)
    try:
        result = serve.serve(params, cfg, requests, mix,
                             conf["serving"]["pool_pages"], clock)
    finally:
        uninstall()
    if clock.t_close is None:
        raise SpecError(f"the mix ran out at step {clock.marks[-1][0]} "
                        f"before the window closed; raise n_requests")
    device = device_info(cell.chips)
    stamped = window.stamp(result, requests, clock)
    win = window.window(stamped, clock)
    failed = sum(1 for r in stamped if len(r.tokens) != r.gen)
    log(f"window: {win.seconds:.3f} s, steps {win.step0}..{win.step1}, "
        f"{win.tokens:.1f} tokens, {len(win.first_in)} first tokens, "
        f"{len(win.done_in)} finished; compiles in window: "
        f"{compiles.in_window} (all run: {compiles.total}, "
        f"{compiles.seconds:.1f} s)")
    e2e = {"tok_s": win.tok_s(), "ttft_p95_s": win.ttft_p95_s(),
           "tpot_p95_ms": win.tpot_p95_ms(),
           "setup_s": clock.t_open - t_start}
    out = {"attempted": len(stamped), "failed": failed, "device": device,
           "window": win,
           "admitted": sorted((r.admitted_step, r.rid) for r in stamped)}
    if traced:
        tr = trace.load(TRACE_DIR)
        data = RunData(win, geo["slots"], Model.from_config(conf), peaks,
                       tr, clock.t_untrace - clock.t_trace,
                       None if recorder.broken else
                       steps.rebuild(recorder.host_calls()))
        if recorder.broken:
            log(f"segment recorder off: {recorder.broken}")
        per_layer = {}
        for m in cell.per_layer:
            v = importlib.import_module(f"metrics.{m['name']}").read(data)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = per_layer
        out["device"].update(busy_s=trace.busy_s(tr),
                             window_s=data.trace_window_s)
        out["breakdown"] = {"device_ops": trace.top_ops(tr),
                            "idle_gaps": trace.idle_gaps(tr)}
    else:
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    del result, recorder
    t_ref = time.perf_counter()
    reading = check.read(check.reference_weights(params), conf,
                         check.sample(stamped, check.SAMPLE_REQUESTS, seed),
                         control=control)
    log(f"reference: {reading.requests} requests, {reading.served} served "
        f"tokens, {time.perf_counter() - t_ref:.1f} s")
    out["reading"] = reading
    return out
