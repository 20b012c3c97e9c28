"""One run of one cell: set-up, the checked first steps, the measured
window, the plain reference, the verdict and the result line.

``run_cell`` is handed the devices; only ``benchmarks/run.py`` looks for a
chip (and refuses anything else), so the tests drive this same code on CPU
devices at a tiny size. Holds no configuration, job, cell or metric name:
they come from ``BENCHMARK.json`` and the files it names (lib/manifest.py).
"""

from __future__ import annotations

import functools
import gc
import math
import os
import shutil
import time

from benchmarks.lib import compare, manifest as mf, peaks, spans as sp
from benchmarks.lib import trace as tr

WARM_STEPS = 3           # after the checked steps, before the window
TRACED_STEPS = 12        # the traced window of a --trace 1 run
LOSS_AT = (0, 10, 20, 50)
COMPILE_EVENTS = "/jax/core/compile/"


def process_age_s() -> float:
    """Seconds since this process was started, interpreter and imports
    included (field 22 of /proc/self/stat against the boot clock)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


class JaxEvents:
    """Counts of what JAX reports about compiling: one listener for the
    life of the process, read as differences."""

    def __init__(self):
        import jax.monitoring as mon

        self.counts: dict = {}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def _on_event(self, event, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def compiles(self) -> int:
        """Traces, lowerings and backend compiles so far."""
        return sum(n for e, n in self.counts.items()
                   if e.startswith(COMPILE_EVENTS))

    def cache(self) -> dict:
        return {e.rsplit("/", 1)[1]: n for e, n in self.counts.items()
                if e.startswith("/jax/compilation_cache/")}


@functools.lru_cache(maxsize=None)
def jax_events() -> JaxEvents:
    """The process's one listener (JAX has no way to take one off)."""
    return JaxEvents()


class Run:
    """What the metric readers are handed (benchmarks/README.md)."""

    def __init__(self, cell: dict, session, spans: sp.Spans, peak: dict):
        self.spans, self.peak, self.chips = spans, peak, cell["chips"]
        self.tokens_per_step = session.tokens_per_step
        self.flops_per_token = session.flops_per_token
        self.kernel_shapes = session.kernel_shapes
        self.setup_s = None
        self.completions: list = []     # host clock, one per finished step
        self.dispatch_s: list = []      # seconds each enqueue took to return
        self.losses: list = []
        self.memory: dict = {}
        self.trace = None               # lib.trace.Trace in a traced run
        self.notes: list = []           # lines a reader wants printed

    def note(self, text: str) -> None:
        self.notes.append(text)

    def intervals(self) -> list:
        """Seconds between consecutive step completions in the window."""
        return [b - a for a, b in zip(self.completions,
                                      self.completions[1:])]

    def tokens_per_s_per_chip(self):
        """Tokens of the steps completed in the window over the time from
        the first completion to the last, per chip: all the work and all
        the time of the window."""
        if len(self.completions) < 2:
            return None
        span = self.completions[-1] - self.completions[0]
        return ((len(self.completions) - 1) * self.tokens_per_step
                / span / self.chips)


def window(session, run: Run, seconds: float, max_steps=None,
           spans: sp.Spans | None = None) -> None:
    """The training loop that logs its loss: enqueue step k+1, then block on
    step k's loss and stamp the clock. The device never waits for the host
    and every step has a completion time. Ends after ``seconds`` (or
    ``max_steps`` completions) and drains the step in flight."""
    clock = time.perf_counter

    def enqueue():
        t0 = clock()
        if spans is None:
            loss = session.step()
        else:
            with spans.span("step.enqueue"):
                loss = session.step()
        run.dispatch_s.append(clock() - t0)
        return loss

    def wait(loss):
        if spans is None:
            value = float(loss)
        else:
            with spans.span("step.wait"):
                value = float(loss)
        run.completions.append(clock())
        run.losses.append(value)

    pending = enqueue()
    begin = clock()
    while True:
        nxt = enqueue()
        wait(pending)
        pending = nxt
        done = len(run.completions)
        if (run.completions[-1] - begin >= seconds
                or (max_steps is not None and done + 1 >= max_steps)):
            break
    wait(pending)


def checked_steps(session, seed: int, steps: int) -> dict:
    """Drive the session from the seed through its first ``steps`` steps,
    through the window's own call and feed, and read what the comparison
    needs: each loss, the first gradient as the optimizer was handed it,
    the parameters' change after the last."""
    program = {"loss": []}
    for i in range(steps):
        program["loss"].append(float(session.step()))
        if i == 0:
            program["grad_norm"] = session.first_gradient_norms()
    program["delta_norm"] = session.delta_norms(seed)
    return program


def device_memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(cell_name: str, *, seed: int, seconds: float, trace: bool,
             devices, manifest: dict | None = None, root: str = mf.ROOT,
             log=print) -> dict:
    """Run the cell on ``devices`` and return the result object of the
    benchmark's last line. Every other number goes through ``log``."""
    import jax

    manifest = mf.load(root) if manifest is None else manifest
    cell = mf.cell(manifest, cell_name)
    config = mf.config_of(manifest, cell["config"], root)
    job = mf.job_of(cell["traffic"], root)
    limits = mf.limits_of(cell_name, root)
    if len(devices) != cell["chips"]:
        raise ValueError(f"cell {cell_name} needs {cell['chips']} devices, "
                         f"was handed {len(devices)}")
    kind = devices[0].device_kind
    peak = peaks.for_device_kind(kind) if devices[0].platform == "tpu" \
        else None
    events = jax_events()
    spans = sp.Spans()
    log(f"[cell] {cell_name}: config {cell['config']} job {cell['traffic']} "
        f"chips {cell['chips']} seed {seed} seconds {seconds} "
        f"trace {int(trace)} device {kind!r}")

    # -- set-up -------------------------------------------------------------
    builder = mf.load_module("builders", config["builder"])
    with spans.span("setup.build"):
        session = builder.build(config, job, devices)
    with spans.span("setup.init"):
        session.init_state(seed)
        session.place_inputs(seed)
    with spans.span("setup.compile"):
        session.compile()
    run = Run(cell, session, spans, peak)
    run.memory = session.memory_analysis()
    structure = session.structure_checks()
    with spans.span("setup.checked_steps"):
        program = checked_steps(session, seed, limits["steps"])
    with spans.span("setup.warm"):
        for _ in range(WARM_STEPS):
            float(session.step())
    gc.collect()
    for name in ("setup.build", "setup.init", "setup.compile",
                 "setup.checked_steps", "setup.warm"):
        log(f"[setup] {name} {spans.total(name):.3f} s")
    log(f"[setup] compile cache {events.cache()}")
    log(f"[setup] step program memory {run.memory}")

    # -- the window -----------------------------------------------------------
    compiles_before = events.compiles()
    run.setup_s = process_age_s()
    if trace:
        trace_dir = os.path.join(root, ".bench_trace", cell_name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans.traced = True
        jax.profiler.start_trace(trace_dir)
        try:
            window(session, run, seconds, max_steps=TRACED_STEPS,
                   spans=spans)
        finally:
            jax.profiler.stop_trace()
            spans.traced = False
        run.trace = tr.load_xplane(tr.find_xplane(trace_dir))
    else:
        window(session, run, seconds)
    compiles_in_window = events.compiles() - compiles_before
    runtime_peak = device_memory_peak(devices)
    intervals = run.intervals()
    log(f"[window] {len(run.completions)} steps completed, "
        f"{len(intervals)} intervals, span "
        f"{run.completions[-1] - run.completions[0]:.3f} s, "
        f"compilations in window {compiles_in_window}")
    shown = [i for i in LOSS_AT if i < len(run.losses)]
    log("[window] loss at window step " + ", ".join(
        f"{i}: {run.losses[i]:.6f}" for i in shown)
        + f", last ({len(run.losses) - 1}): {run.losses[-1]:.6f}")

    # -- the verdict ----------------------------------------------------------
    session.release()
    t0 = time.perf_counter()
    reference = session.reference(seed, limits["steps"])
    log(f"[check] plain reference over {limits['steps']} steps: "
        f"{time.perf_counter() - t0:.1f} s (not part of setup_s)")
    rows = compare.judge(program, reference, limits)
    for name, value, limit, ok, note in rows:
        log(f"[check] {name} {value:.6g} limit {limit:.6g} "
            f"{'ok' if ok else 'FAIL'} ({note})")
    log("[check] loss program " + " ".join(f"{v:.6f}" for v in
                                           program["loss"])
        + " | reference " + " ".join(f"{v:.6f}" for v in reference["loss"]))
    for name, value, limit, ok in structure:
        log(f"[check] {name} {value} limit {limit} "
            f"{'ok' if ok else 'FAIL'}")
    failed = sum(1 for v in program["loss"] + run.losses
                 if not math.isfinite(v))
    log(f"[check] non_finite_losses {failed} limit 0 "
        f"{'ok' if failed == 0 else 'FAIL'}")
    log(f"[check] compilations_in_window {compiles_in_window} limit 0 "
        f"{'ok' if compiles_in_window == 0 else 'FAIL'}")
    correct = (all(r[3] for r in rows) and all(r[3] for r in structure)
               and failed == 0 and compiles_in_window == 0)

    # -- the result line ------------------------------------------------------
    section, readers = (("per_layer", "layers") if trace
                        else ("end_to_end", "end_to_end"))
    metrics = {}
    for m in mf.metrics_for(manifest, section, cell_name):
        value = mf.load_module(readers, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for line in run.notes:
        log(f"[metric] {line}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(runtime_peak, run.memory["total"])}
    log(f"[device] runtime peak_bytes_in_use {runtime_peak}, compiled step "
        f"total {run.memory['total']} (this runtime's counter leaves a "
        f"program's temporaries out; the larger is reported)")
    result = {"correct": bool(correct),
              "attempted": len(program["loss"]) + len(run.losses),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.mean_busy_seconds(run.trace)
        device["window_s"] = run.trace.window[1] - run.trace.window[0]
        first = min(run.trace.ops)
        result["breakdown"] = {
            "device_ops": tr.top_ops(run.trace, first),
            "idle_gaps": tr.idle_gaps(run.trace, first)}
    return result
