"""One run of one benchmark cell: set-up, measured window, correctness.

The window drives the program's own training loop, ``repro.train.trainer.
Trainer.train``, over an ``Overlord`` built from the cell's traffic file.
The harness copies no part of that loop.  It sees the loop only through
the instances it built: it wraps their methods (``Trainer.fetch``,
``Trainer.step``, ``Overlord.get_batch``, ``Overlord.step_done``), reads
``Trainer.history`` and the Overlord's telemetry spans, and in a traced
run reads the profiler's trace.  The program is given weights the
benchmark draws from the seed (the configuration's model family,
``bench/models/<family>.py``: ``init_params``, handed to the trainer
through the model's ``init``), so the reference can draw the same.

Set-up ends when the cell's first ``warm_steps`` steps are done; they go
through the window's own call and feed, compile the step, and give the
readings the reference follows.  The window starts there and ends at the
first step done ``seconds`` later.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time
from typing import Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, ".data")


# ----------------------------------------------------------------- files
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell(workload: str) -> dict:
    """Everything BENCHMARK.json and the files it names say of a cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload,
        "chips": wl["chips"],
        "config": load_json(ROOT, conf_entry["file"]),
        "traffic_name": wl["traffic"],
        "traffic": load_json(BENCH, "traffic", wl["traffic"] + ".json"),
        "limits": load_json(BENCH, "limits", workload + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def model_config(conf: dict):
    """The registered arch with every model field the file gives."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name"}
    return base.replace(name=conf["arch"] + "-bench",
                        **{k: v for k, v in conf.items() if k in fields})


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seeds(seed: int) -> dict:
    """Seeds for each part, from any whole number ``seed``."""
    state = np.random.SeedSequence(seed).generate_state(1)
    return {"data": seed, "params": int(state[0] & 0x7FFFFFFF)}


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def rss_bytes() -> int:
    """The process's resident set now (``/proc/self/statm``, in pages)."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class RssSampler:
    """The process's resident set, read every ``every`` seconds on a thread
    of its own from ``start`` to ``stop``: its value at the start and the
    largest read."""

    def __init__(self, every: float = 0.05):
        self.every = every
        self.start_bytes = self.peak_bytes = None
        self._stop = threading.Event()
        self._thread = None

    def start(self) -> None:
        self.start_bytes = self.peak_bytes = rss_bytes()
        self._thread = threading.Thread(target=self._run, name="bench-rss",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.every):
            self.peak_bytes = max(self.peak_bytes, rss_bytes())

    def stop(self) -> None:
        if self._thread is not None and not self._stop.is_set():
            self._stop.set()
            self._thread.join()
            self.peak_bytes = max(self.peak_bytes, rss_bytes())


# ---------------------------------------------------------------- set-up
@contextlib.contextmanager
def overlord(cfg, mix: dict, paths: dict):
    """A started Overlord over the mix's sources, built as
    ``repro.launch.train.overlord_for`` builds one."""
    from repro.core import ClientPlaceTree, Overlord, OverlordConfig, \
        StaticSchedule
    from repro.data.cost_models import backbone_cost
    tree = ClientPlaceTree([("PP", 1), ("DP", mix["dp"]), ("CP", 1),
                            ("TP", 1)])
    ov = Overlord(paths, tree, StaticSchedule(dict(mix["weights"])),
                  OverlordConfig(
                      seq_len=mix["seq_len"],
                      rows_per_microbatch=mix["rows"], n_bins=mix["bins"],
                      strategy=mix["strategy"],
                      strategy_params={"broadcast": (),
                                       "costfn": backbone_cost(cfg)},
                      vocab_size=cfg.vocab_size, **mix.get("overlord", {})))
    ov.start()
    try:
        yield ov
    finally:
        ov.shutdown()


class WindowClosed(Exception):
    """Raised from the wrapped ``step_done`` to end ``Trainer.train``."""


class Probe:
    """Wraps the trainer's and the Overlord's methods to time the window
    and keep what the checks and the metric readers need."""

    def __init__(self, trainer, ov, conf: dict, key_seed: int, warm: int,
                 seconds: float, trace_dir: Optional[str]):
        import jax
        self.jax = jax
        self.trainer, self.ov, self.conf = trainer, ov, conf
        self.key_seed, self.warm, self.seconds = key_seed, warm, seconds
        self.trace_dir = trace_dir
        self.batches: dict[int, dict] = {}
        self.views: dict[int, list] = {}
        self.t0 = self.t_end = None
        self.last_step = None
        self.readings: dict = {}
        self.step_calls = 0
        self.rss = RssSampler()
        for name in ("fetch", "step"):
            setattr(trainer, name, self._wrap(getattr(trainer, name), name))
        for name in ("get_batch", "step_done"):
            setattr(ov, name, self._wrap(getattr(ov, name), name))

    def _annotate(self, name: str, **kw):
        if self.trace_dir and self.t0 is not None:
            return self.jax.profiler.TraceAnnotation("bench." + name, **kw)
        return contextlib.nullcontext()

    def _wrap(self, fn, name):
        return getattr(self, "_" + name)(fn)

    def _fetch(self, fn):
        def fetch(step):
            with self._annotate("fetch", step=step):
                batch = fn(step)
            self.batches[step] = batch
            return batch
        return fetch

    def _get_batch(self, fn):
        def get_batch(step, rank, *a, **kw):
            view = fn(step, rank, *a, **kw)
            if view.get("role") == "data" and view.get("cp_rank", 0) == 0:
                self.views.setdefault(step, []).append(
                    (rank, [list(row) for b in view["bins"]
                            for row in b.doc_ids]))
            return view
        return get_batch

    def _step(self, fn):
        def step(batch):
            with self._annotate("step"):
                rec = fn(batch)
            self.step_calls += 1
            if self.step_calls == 1:
                self.readings["grad_norms"] = self._grad_norms()
            if self.step_calls == self.warm:
                self.readings["update_norms"] = self._update_norms()
            return rec
        return step

    def _step_done(self, fn):
        def step_done(step, metrics=None):
            with self._annotate("step_done", step=step):
                fn(step, metrics)
            now = time.perf_counter()
            if step == self.warm - 1:
                if self.trace_dir:
                    self.jax.profiler.start_trace(self.trace_dir)
                self.rss.start()
                self.t0 = time.perf_counter()
            elif self.t0 is not None and now - self.t0 >= self.seconds:
                self.t_end, self.last_step = now, step
                self.rss.stop()
                raise WindowClosed
        return step_done

    # program-side readings for the reference comparison
    def _grad_norms(self) -> dict:
        """The first gradient as the optimizer got it: Adam's first moment
        after one step is (1 - b1) times the clipped gradient."""
        from bench.reference import leaf_norms
        scale = 1.0 / (1.0 - self.conf["optimizer"]["b1"])
        norms = self.jax.jit(leaf_norms)(self.trainer.state.opt.mu)
        return {k: float(v) * scale for k, v in norms.items()}

    def _update_norms(self) -> dict:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from bench.reference import family, leaf_norms
        jax = self.jax
        init_params = family(self.conf["family"]).init_params
        p0 = jax.jit(lambda k: init_params(self.conf, k),
                     out_shardings=NamedSharding(self.trainer.mesh, P()))(
            jax.random.key(self.key_seed))
        norms = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jax.numpy.subtract, a, b)))(
                self.trainer.state.params, p0)
        del p0
        return {k: float(v) for k, v in norms.items()}


# ----------------------------------------------------------------- checks
def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's |program norm - reference norm| over the larger of
    that leaf's reference norm and the median leaf's."""
    names = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in ref)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in names)


def verdict(numbers: dict, limits: dict) -> tuple[dict, bool]:
    """Each number beside its limit, and whether every one is within it."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return checks, all(v["value"] <= v["limit"] for v in checks.values())


def compare(probe: Probe, mix: dict, seed: int, vocab: int, ref: dict,
            history: list) -> dict:
    """Every number the correctness check compares."""
    from bench.reference import expected_rows
    from bench.sources import record_tokens
    tokens_of = record_tokens(mix, seed, vocab)
    rows_wrong, tokens_gap = 0, 0.0
    for rec in history:
        step = rec["step"]
        got = probe.batches[step]
        doc_rows = [row for _, rows in probe.views[step] for row in rows]
        try:
            exp = expected_rows(doc_rows, tokens_of, mix["seq_len"])
        except (KeyError, ValueError):
            rows_wrong += len(doc_rows)
            continue
        n = len(doc_rows)
        bad = np.zeros(max(n, got["tokens"].shape[0]), bool)
        for k, a in exp.items():
            g = got[k]
            if g.shape != a.shape:
                bad[:] = True
                break
            bad[:n] |= np.any(g != a, axis=1)
        rows_wrong += int(bad.sum())
        tokens_gap = max(tokens_gap, abs(rec["tokens"] - float(np.sum(
            (exp["labels"] >= 0) & (exp["segment_ids"] > 0)))))
    numbers = {"rows_wrong": float(rows_wrong),
               "tokens_gap": float(tokens_gap)}
    numbers.update(model_numbers(
        [r["loss"] for r in history[:len(ref["losses"])]],
        probe.readings["grad_norms"], probe.readings["update_norms"], ref))
    return numbers


def model_numbers(losses: list, grad_norms: dict, update_norms: dict,
                  ref: dict) -> dict:
    """Each step's loss, the first clipped gradient's leaf norms and the
    leaf norms of the weights' change over the reference's steps, against
    the reference's.  A leaf whose reference gradient is under a
    thousandth of the median leaf's is left out of the change."""
    med = statistics.median(ref["grad_norms"].values())
    moved = {k for k, g in ref["grad_norms"].items() if g >= 1e-3 * med}
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref["losses"])),
        "grad_gap": leaf_gap(grad_norms, ref["grad_norms"]),
        "update_gap": leaf_gap(update_norms, ref["update_norms"], moved),
    }


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class Window:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    conf: dict             # the benchmark's configuration file
    chips: int
    device_kind: str
    setup_s: float
    window_s: float
    steps: list            # Trainer.history records inside the window
    batches: dict          # step -> the global batch Trainer.fetch made
    spans: list            # telemetry spans that started in the window
    rss_start_bytes: int   # resident set when the window opened
    rss_peak_bytes: int    # the largest read inside the window
    plane_bytes: float
    trace: Optional[dict]


@contextlib.contextmanager
def training(c: dict, seed: int, devices):
    """The cell's trainer over a started Overlord, as the window gets it:
    sources written for ``seed``, the benchmark's weights, the program's
    Trainer.  Yields (model config, seeds, overlord, trainer)."""
    import jax
    from repro.launch.cache import enable_compile_cache
    from repro.models.model_zoo import Model, build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig
    from bench import sources
    from bench.reference import family

    enable_compile_cache()
    conf, mix = c["config"], c["traffic"]
    cfg = model_config(conf)
    init_params = family(conf["family"]).init_params
    sd = seeds(seed)
    paths = sources.materialize(
        mix, sd["data"], os.path.join(DATA, f"{c['traffic_name']}-{seed}"))

    class BenchModel(Model):
        def init(self, key, dtype=None):
            return init_params(conf, key)

    base = build_model(cfg)
    model = BenchModel(**{f.name: getattr(base, f.name)
                          for f in dataclasses.fields(Model)})
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                        base.abstract_params(jax.numpy.float32))
    have = jax.tree.map(lambda x: (x.shape, str(x.dtype)), jax.eval_shape(
        lambda k: init_params(conf, k), jax.random.key(0)))
    if want != have:
        raise RuntimeError("the benchmark's weights do not match the "
                           f"program's parameter tree: {want} != {have}")
    big = 1 << 40
    with overlord(cfg, mix, paths) as ov:
        trainer = Trainer(model, ov, TrainerConfig(
            steps=big, log_every=big, ckpt_every=big,
            opt=AdamWConfig(**conf["optimizer"])),
            seed=sd["params"], devices=list(devices))
        try:
            yield cfg, sd, ov, trainer
        finally:
            trainer.state = trainer._compiled = trainer.step_fn = None
            gc.collect()


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None) -> dict:
    import jax
    from bench.reference import family

    conf, mix = c["config"], c["traffic"]
    devices = devices or jax.devices()[:c["chips"]]
    trace_dir = os.path.join(DATA, "trace", f"{c['name']}-{seed}") \
        if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    with training(c, seed, devices) as (cfg, sd, ov, trainer):
        probe = Probe(trainer, ov, conf, sd["params"], mix["warm_steps"],
                      seconds, trace_dir)
        try:
            trainer.train(1 << 40)
        except WindowClosed:
            pass
        finally:
            probe.rss.stop()
            if trace_dir and probe.t0 is not None:
                jax.profiler.stop_trace()
        if probe.t_end is None:
            raise RuntimeError("the window never closed")
        history = list(trainer.history)
        compile_s = trainer.compile_s
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        plane = float(ov.memory_report()["total_ex_shadows"])
        tracer = ov.telemetry.tracer
        spans = [s for s in tracer.finished()
                 if probe.t0 <= s.start <= probe.t_end]
        dropped = tracer.dropped
    log(f"setup {probe.t0 - t_start:.2f}s, window {probe.t_end - probe.t0:.2f}s"
        f" over steps {mix['warm_steps']}..{probe.last_step}, "
        f"peak {peak} B, compile {compile_s}s, "
        f"{len(spans)} spans in the window, {dropped} dropped")

    in_window = [r for r in history if r["step"] >= mix["warm_steps"]]
    reduced = None
    if trace_dir:
        from bench import trace as tr
        path = tr.find_xplane(trace_dir)
        reduced = tr.reduce(tr.load(path)) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = Window(
        conf=conf, chips=len(devices),
        device_kind=devices[0].device_kind, setup_s=probe.t0 - t_start,
        window_s=probe.t_end - probe.t0, steps=in_window,
        batches={r["step"]: probe.batches[r["step"]] for r in in_window},
        spans=spans, rss_start_bytes=probe.rss.start_bytes,
        rss_peak_bytes=probe.rss.peak_bytes, plane_bytes=plane, trace=reduced)

    t_ref = time.perf_counter()
    ref = family(conf["family"]).train(conf, conf["optimizer"],
                          jax.random.key(sd["params"]),
                          [probe.batches[s] for s in range(mix["warm_steps"])],
                          "f32", device=devices[0])
    numbers = compare(probe, mix, sd["data"], cfg.vocab_size, ref, history)
    log(f"reference and checks {time.perf_counter() - t_ref:.2f}s")
    checks, correct = verdict(numbers, c["limits"])

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in c[kind]:
        value = reader(m["name"])(window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": len(in_window),
           "failed": sum(1 for r in in_window if not math.isfinite(r["loss"])),
           "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out

