"""Driver for ``kind: serve`` traffic: the program's own server
(``serve.parse_args`` / ``serve.main``, single mode, Unix socket) in this
process, which holds the chip; the load generator as a child; a conductor
thread between them.  Arrival ``poisson | uniform | burst | closed`` and
every size and rate come from the traffic file.

Nothing of the program is changed.  One seam is used: the server takes its
weights from ``serve.eval_params_from_args``, and this driver puts the
benchmark's weights (the configuration's ``weights`` module, from
``--seed``) behind that name for the run, after checking that the program's
tree has exactly the reference's leaves.

What belongs to one architecture — weights, plain reference, comparison,
operation counts — this driver takes from ``harness.modules_of(config)``
and knows by no other name (README, "A configuration").
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

from benchmark import harness, loadgen, xplane
from benchmark.weights import as_tree, check_against

TRACE_AT = 0.3          # the traced stretch starts this far into the window
TRACE_SECONDS = 4.0     # and lasts this long (or 40 % of a short window)


def server_argv(config: dict, sock: str) -> list:
    argv = ["--network", config["network"], "--dataset", config["dataset"],
            "--unix-socket", sock, *config["serve_flags"]]
    for item in config["cfg"]:
        argv += ["--cfg", item]
    return argv


def _get_metrics(sock: str) -> dict:
    status, doc = loadgen.http_unix(sock, "GET", "/metrics", timeout=30)
    if status != 200 or not isinstance(doc, dict):
        raise RuntimeError(f"/metrics answered {status}")
    return doc


class Conductor(threading.Thread):
    """Starts the generator, follows its events, snapshots ``/metrics``
    around the window, traces a stretch of it when asked, reads the device's
    peak memory, then asks the server for its graceful stop."""

    def __init__(self, spec: dict, sock: str, trace_dir: str | None,
                 chips: int):
        super().__init__(name="bench-conductor", daemon=True)
        self.spec, self.sock, self.trace_dir = spec, sock, trace_dir
        self.chips = chips
        self.out: dict = {}
        self.error: BaseException | None = None
        self.child: subprocess.Popen | None = None
        self._handler0 = signal.getsignal(signal.SIGTERM)

    def run(self):
        try:
            self._drive()
        except BaseException as e:  # noqa: BLE001 — re-raised by the driver
            self.error = e
        finally:
            if self.child is not None and self.child.poll() is None:
                self.child.kill()
                self.child.wait()
            # the server's own drain — once it has taken the handler: a
            # SIGTERM before that would end the whole process
            t_end = time.monotonic() + 900.0
            while (signal.getsignal(signal.SIGTERM) is self._handler0
                   and time.monotonic() < t_end):
                time.sleep(0.1)
            os.kill(os.getpid(), signal.SIGTERM)

    def _trace(self, t0: float, seconds: float):
        import jax

        length = min(TRACE_SECONDS, 0.4 * seconds)
        lag = t0 + TRACE_AT * seconds - time.monotonic()
        if lag > 0:
            time.sleep(lag)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host frames cost more than they tell
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t_a = time.monotonic()
        with jax.profiler.TraceAnnotation(xplane.MARK_BEGIN):
            pass
        time.sleep(length)
        with jax.profiler.TraceAnnotation(xplane.MARK_END):
            pass
        self.out["trace_host_window_s"] = time.monotonic() - t_a
        jax.profiler.stop_trace()

    def _drive(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")   # the child never
        self.child = subprocess.Popen(                # touches the chip
            [sys.executable, os.path.join(harness.HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=harness.ROOT)
        self.child.stdin.write(json.dumps(self.spec) + "\n")
        self.child.stdin.close()
        tracer = None
        for line in self.child.stdout:
            doc = json.loads(line)
            if doc["event"] == "window":
                self.out["t0"] = doc["t0"]
                self.out["metrics_before"] = _get_metrics(self.sock)
                if self.trace_dir:
                    tracer = threading.Thread(
                        target=self._trace, args=(doc["t0"], doc["seconds"]),
                        name="bench-tracer", daemon=True)
                    tracer.start()
            elif doc["event"] == "result":
                self.out["result"] = doc
                # the second snapshot now, window + drain after the first:
                # not after the child's exit and the tracer's join
                self.out["metrics_after"] = _get_metrics(self.sock)
        rc = self.child.wait()
        if tracer is not None:
            tracer.join()
        if rc != 0 or "result" not in self.out:
            raise RuntimeError(f"load generator exited {rc} without a result")
        self.out["memory_peak_bytes"] = harness.memory_peak_bytes(self.chips)


def serve_window(spec: dict, seed: int, seconds: float, trace: bool,
                 flat: dict, t_start: float, conductor=None) -> dict:
    """Run the server with the benchmark's weights under the cell's traffic
    for one window -> the conductor's findings (and ``setup_s``).
    ``conductor``: a ``Conductor`` subclass (the rate sweep's)."""
    import jax
    import serve
    from mx_rcnn_tpu.models.detector import init_params

    config, traffic, cell = spec["config"], spec["traffic"], spec["cell"]

    def benchmark_params(args, cfg, model):
        shapes = jax.eval_shape(
            lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
        check_against(flat, shapes)
        return as_tree(flat)

    trace_dir = None
    if trace:
        trace_dir = os.path.join(harness.TMP_DIR, "trace-" + cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    handlers = {s: signal.getsignal(s) for s in (signal.SIGTERM,
                                                 signal.SIGINT)}
    program_params = serve.eval_params_from_args
    tmp = tempfile.mkdtemp(prefix="mxrb_")
    try:
        sock = os.path.join(tmp, "s.sock")
        args = serve.parse_args(server_argv(config, sock))
        gen_spec = {"traffic": traffic, "socket": sock, "seed": seed,
                    "seconds": seconds,
                    "num_classes": config["net"]["num_classes"]}
        cond = (conductor or Conductor)(gen_spec, sock, trace_dir,
                                        cell["chips"])
        serve.eval_params_from_args = benchmark_params
        cond.start()
        try:
            serve.main(args)      # returns once the conductor's SIGTERM
        finally:                  # has drained it
            serve.eval_params_from_args = program_params
            cond.join(timeout=120)
            for s, h in handlers.items():
                signal.signal(s, h)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if cond.error is not None:
        raise cond.error
    out = cond.out
    out["setup_s"] = out["t0"] - t_start
    out["trace_dir"] = trace_dir
    return out


class Warmer(Conductor):
    """The conductor of the pre-compile child: no window.  Once the server
    is ready (both programs built and warmed) the generator's own warm
    requests, so that whatever a first request builds is in the cache too;
    then the stop."""

    def _drive(self):
        traffic = self.spec["traffic"]
        mix = traffic["bodies"]
        loadgen.wait_ready(self.sock, 1000.0)
        loadgen.warm(self.sock, loadgen.make_bodies(mix, self.spec["seed"]),
                     loadgen.body_sizes(mix), int(self.spec["num_classes"]),
                     int(traffic.get("warm_per_orientation", 16)), 600.0)
        self.out["t0"] = time.monotonic()


def precompile(spec: dict, seed: int) -> None:
    """Everything a run of this cell builds before its window, built once
    and left in the persistent cache: the weights' program and the server's
    (``run.py --precompile``, a child of the first run in a checkout)."""
    config = spec["config"]
    flat = harness.modules_of(config)["weights"].make(config["net"], seed)
    serve_window(spec, seed, 0.0, False, flat, time.monotonic(),
                 conductor=Warmer)


def after_window(mods: dict, config: dict, flat: dict, res: dict,
                 metrics: dict, device: dict, breakdown=None):
    """What decides ``correct``, once the window has closed and the server
    is gone: the configuration's reference over each sampled request, its
    comparison over the sample's whole responses, its judgement against the
    configuration's limits -> (the result's last line, {name: (value,
    limit)})."""
    net = config["net"]
    t_ref = time.monotonic()
    dense = [mods["reference"].detect(flat, s["doc"], net)
             for s in res["sample"]]
    numbers = mods["compare"].compare(res["sample"], dense, net)
    print(f"reference: {len(dense)} requests in "
          f"{time.monotonic() - t_ref:.1f} s", file=sys.stderr)
    ok, compared = mods["compare"].judge(numbers, config["correct"])
    line = harness.result_line(ok, res["attempted"], res["failed"], metrics,
                               device, {k: {"value": v, "limit": l}
                                        for k, (v, l) in compared.items()},
                               breakdown)
    return line, compared


def stage_means(before: dict, after: dict) -> dict:
    """{stage: [observations, ms an observation]} between two ``/metrics``
    snapshots, for every stage clock the program keeps: a line on standard
    error in every run, traced or not, for whoever reads a set's spread."""
    out = {}
    zero = {"count": 0, "sum_s": 0.0}
    b = before.get("stages") or {}
    for name, a in sorted((after.get("stages") or {}).items()):
        n = a["count"] - b.get(name, zero)["count"]
        if n > 0:
            out[name] = [n, round(1e3 * (a["sum_s"] - b.get(name, zero)[
                "sum_s"]) / n, 3)]
    return out


def run(spec: dict, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float):
    """-> (the result's last line, {name: (value, limit)})."""
    config, cell, bench = spec["config"], spec["cell"], spec["bench"]
    mods = harness.modules_of(config)
    flat = mods["weights"].make(config["net"], seed)
    out = serve_window(spec, seed, seconds, trace, flat, t_start)
    res = out["result"]
    print(f"window: {json.dumps({k: v for k, v in res.items() if k != 'sample'})}",
          file=sys.stderr)
    print(f"setup_s {out['setup_s']:.3f}  memory_peak_bytes "
          f"{out['memory_peak_bytes']}", file=sys.stderr)
    print("stages: " + json.dumps(stage_means(out["metrics_before"],
                                              out["metrics_after"])),
          file=sys.stderr)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])

    breakdown = None
    if trace:
        planes = xplane.load(xplane.find_trace(out["trace_dir"]))
        red = xplane.reduce(planes, cell["chips"],
                            out.get("trace_host_window_s"))
        shutil.rmtree(out["trace_dir"], ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx = {"trace": red, "window": res, "config": config,
               "traffic": spec["traffic"], "cell": cell,
               "peaks": harness.peaks_for(device["kind"]),
               "metrics_before": out["metrics_before"],
               "metrics_after": out["metrics_after"],
               "flops": mods["flops"]}
        metrics = harness.read_layers(bench, cell["name"], ctx)
    else:
        metrics = {}
        for m in harness.metrics_of(bench, "end_to_end", cell["name"]):
            value = out["setup_s"] if m["name"] == "setup_s" else res[m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # the program's state is gone with serve.main; what is left on the chip
    # is the benchmark's weights.  Now the reference, request by request.
    gc.collect()
    return after_window(mods, config, flat, res, metrics, device, breakdown)
