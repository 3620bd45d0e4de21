"""One run of one cell: set up the served path, measure a window of
traffic, check every answer against the plain reference, and reduce the
metrics the cell reports.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the configuration as it is run, and the name
  of its plain reference, ``configs/<reference>.py``;
* ``traffic/<traffic>.json``: the parameters ``loadgen`` reads, and the
  name of the loop that drives the window, ``loops/<loop>.py``, with its
  own.  With ``"profile_store": true`` the plan warm-starts from the
  profile that the checkout's first run of the cell measured and kept in
  ``STORE``;
* ``metrics/<metric>.py``: a reader, ``read(run) -> float | None``.  A
  metric ``<stem>.<part>`` without a file of its own is read by
  ``metrics/<stem>.py``.

From the system under test this takes only its entry point,
``repro.api.Deployment``, and the packing of weights into its own format.
"""

from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

import loadgen
import ops
import trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
STORE = ROOT / ".onchip_store"   # the checkout's profile store
REF_BLOCK = 512          # images per reference call


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the cell's own configuration
    traffic: dict
    metrics: list          # [(name, unit)] for --trace 0
    per_layer: list        # [(name, unit)] for --trace 1
    tenants: dict = dataclasses.field(default_factory=dict)  # name -> config


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric that lists its cells applies to those; one that does not
    applies to every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file.name}")
    w = cells[workload]
    files = {c["name"]: ROOT / c["file"] for c in bench["configs"]}
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    tenants = {
        name: json.loads(files[name].read_text())
        for name in [w["config"], *traffic.get("tenants", [])]
    }
    e2e = [
        (m["name"], m["unit"]) for m in bench["end_to_end"]
        if _applies(m, workload, set())
    ]
    reported = {name for name, _ in e2e}
    per_layer = [
        (m["name"], m["unit"]) for m in bench["per_layer"]
        if _applies(m, workload, reported)
    ]
    return Cell(workload, int(w["chips"]), tenants[w["config"]], traffic,
                e2e, per_layer, tenants)


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(metric: str) -> Path:
    own = BENCH / "metrics" / f"{metric}.py"
    return own if own.exists() else BENCH / "metrics" / f"{metric.split('.')[0]}.py"


def reader(metric: str):
    return _load_module(reader_path(metric)).read


def loop(traffic: dict):
    """The loop that drives the window, as the traffic mix names it."""
    return _load_module(BENCH / "loops" / f"{traffic['loop']}.py")


def reference(config: dict):
    return _load_module(BENCH / "configs" / f"{config['reference']}.py")


def seed_key(seed: int):
    import jax

    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


@dataclasses.dataclass
class RunData:
    """What a metric reader may read."""

    cell: Cell
    device_kind: str
    setup_s: float
    window_s: float
    completed: int                 # images completed in the window
    latency_s: list                # per completed request, all tenants
    spans: list                    # [(name, t0, t1)], traced runs only
    trace: object                  # trace_reduce.Summary or None
    macs: dict                     # tenant -> binary MACs per image
    completed_by: dict             # tenant -> images completed in the window


def _engine(dep, tenant: str):
    return dep.engine if dep.engine is not None else \
        dep.router.tenant(tenant).engine


def plan_signature(dep, tenant: str, words) -> dict:
    """A tenant's served plan: per-layer configs, proper batch, fused
    segments, and each node's placement and the device its output lands
    on (one pass over the nodes, at the served batch, as the serial
    path runs them)."""
    import jax

    ec = dep.configuration(tenant)
    pipe = _engine(dep, tenant).pipeline
    x = words[: ec.proper_batch_size]
    nodes = []
    for node, fn in pipe.segment_fns:
        x = jax.device_put(np.asarray(x), pipe.device) if node.on_device \
            else np.asarray(x)
        out = fn(x)
        jax.block_until_ready(out)
        nodes.append([
            node.start, node.stop, node.placement,
            node.fused_variant or "+".join(node.configs),
            ",".join(sorted(str(d) for d in out.devices())),
        ])
        x = out
    sig = {
        "proper_batch": ec.proper_batch_size,
        "layers": list(ec.layer_configs),
        "fused": [[s, e, v] for s, e, v, _ in ec.fused_segments],
        "nodes": nodes,
    }
    sig["hash"] = hashlib.sha256(
        json.dumps(sig, sort_keys=True).encode()
    ).hexdigest()[:12]
    return sig


class _CompileCounter:
    """Counts JAX compile and trace events while `active`."""

    def __init__(self):
        import jax.monitoring as mon

        self.active, self.n = False, 0
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and ("compile" in event or "trace_duration" in event):
            self.n += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)


class _GcTimer:
    """Python's garbage-collection pauses while `active`: count and
    seconds, so that a stall in a window can be told apart from one in
    the system."""

    def __init__(self):
        self.active, self.n, self.s, self._t0 = False, 0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.active and self._t0 is not None:
            self.n += 1
            self.s += time.perf_counter() - self._t0

    def close(self):
        gc.callbacks.remove(self._on)


def compare(ref_scores: np.ndarray, index, scores) -> dict:
    """Each answer against the reference's scores for its image.  An
    answer that never came, or came as an error, is a failed request."""
    ok = [k for k, s in enumerate(scores) if s is not None]
    got = np.stack([np.asarray(scores[k]) for k in ok]) if ok else \
        np.zeros((0, ref_scores.shape[1]), np.int32)
    want = ref_scores[np.asarray([index[k] for k in ok], dtype=np.int64)]
    gap = np.abs(got.astype(np.int64) - want)
    return {
        "checked": len(ok),
        "failed_requests": len(scores) - len(ok),
        "mismatched_responses": int((gap.max(axis=1) > 0).sum()) if ok else 0,
        "max_score_gap": int(gap.max()) if ok else 0,
    }


def judge(numbers: dict) -> tuple:
    """`correct`, and each number compared beside its limit (all exact:
    the limit is 0)."""
    checks = {
        name: {"value": numbers[name], "limit": 0}
        for name in ("mismatched_responses", "max_score_gap", "failed_requests")
    }
    correct = numbers["checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values()
    )
    return correct, checks


def reference_scores(config: dict, params_host, x01: np.ndarray, *,
                     sum_dtype=None) -> np.ndarray:
    """The plain reference over `x01`, in blocks, as int32 scores."""
    import jax

    ref = reference(config)
    params = jax.device_put(params_host)
    fwd = jax.jit(
        lambda p, x: ref.forward(config, p, x, sum_dtype=sum_dtype)
    )
    out = []
    for b in range(0, len(x01), REF_BLOCK):
        block = ref.binarize_input(x01[b:b + REF_BLOCK])
        out.append(np.asarray(fwd(params, block)))
    return np.rint(np.concatenate(out)).astype(np.int64)


def make_weights(config: dict, seed: int):
    """The configuration's weights from `seed`, in one jitted call on
    the device, then on the host."""
    import jax

    ref = reference(config)
    return jax.device_get(
        jax.jit(lambda k: ref.init_params(config, k))(seed_key(seed))
    )


def _memory(devices, key: str) -> int:
    return max((d.memory_stats() or {}).get(key, 0) for d in devices)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, plan_kwargs: dict | None = None, log=print) -> tuple:
    """One run.  Returns the result object and the compared numbers."""
    import jax

    from repro import api
    from repro.bnn import build_model
    from repro.bnn.models import pack_params

    traffic = cell.traffic
    window = loop(traffic)
    devices = jax.devices()[: cell.chips]

    # every tenant's weights and image pool from the seed
    weights, pools, images, models = {}, {}, {}, {}
    for name, cfg in cell.tenants.items():
        weights[name] = make_weights(cfg, seed)
        model = build_model(cfg["model"], scale=cfg["scale"])
        notation = [s.notation for s in model.specs]
        if notation != list(cfg["layers"]):
            raise ValueError(f"the program builds {notation}, not {cfg['layers']}")
        images[name] = loadgen.make_images(
            seed % 2**64, int(traffic["pool"]), cfg["input_hw"],
            cfg["in_channels"],
        )
        pools[name] = loadgen.pack_images(images[name])
        models[name] = (model, pack_params(model.specs, weights[name]))

    kwargs = dict(batch_sizes=(int(traffic["batch"]),), autotune=True,
                  fuse=True, time_source="measured")
    if traffic.get("profile_store"):
        kwargs["store"] = str(STORE)
    kwargs.update(plan_kwargs or {})
    dep = api.Deployment.plan(models, **kwargs)
    dep.serve()
    for name, tp in dep.tenants.items():
        log(f"phase_s {name}: "
            + json.dumps({k: round(v, 4) for k, v in tp.phase_s.items()}))

    requests = loadgen.requests(seed % 2**64, pools)
    window.warm(dep, requests, traffic)
    plans = {}
    for name in dep.tenants:
        sig = plan_signature(dep, name, pools[name])
        plans[name] = sig["hash"]
        log(f"plan_signature {name}: " + json.dumps(sig))
    gc.collect()
    log(f"bytes_in_use after warm-up: {_memory(devices, 'bytes_in_use')} "
        f"of {_memory(devices, 'bytes_limit')}")
    setup_s = time.perf_counter() - t_start
    log(f"setup_s: {setup_s:.4f}")

    spans = loadgen.Spans(annotate=trace)
    counter, pauses = _CompileCounter(), _GcTimer()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="onchip-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.active = pauses.active = True
    try:
        out = window.drive(dep, requests, traffic, seconds=seconds,
                           spans=spans, rng=np.random.default_rng([seed % 2**64, 3]))
    finally:
        counter.active = pauses.active = False
        counter.close()
        pauses.close()
        if trace:
            jax.profiler.stop_trace()
    log(f"compiles_in_window: {counter.n}")
    log(f"gc_in_window: {pauses.n} collections, {pauses.s:.4f} s")
    if out["done"]:
        log(f"latency_max_ms: {max(lat for *_, lat in out['done']) * 1e3:.3f}")
    if out["shed"]:
        log(f"shed_requests: {len(out['shed'])}")
    summary = None
    if trace:
        t0 = time.perf_counter()
        (pb,) = Path(trace_dir).rglob("*.xplane.pb")
        log(f"trace: {pb.stat().st_size} bytes")
        summary = trace_reduce.reduce(trace_reduce.load(pb))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.2f}s")

    peak = _memory(devices, "peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak}")
    del dep, models
    gc.collect()

    # every answer of the window, and the late ones, against the reference
    answers = [(t, i, s) for t, i, s, _ in out["done"]] + out["late"]
    numbers = {"checked": 0, "failed_requests": 0,
               "mismatched_responses": 0, "max_score_gap": 0}
    for name, cfg in cell.tenants.items():
        mine = [(i, s) for t, i, s in answers if t == name]
        got = compare(
            reference_scores(cfg, weights[name], images[name]),
            [i for i, _ in mine], [s for _, s in mine],
        )
        for k, v in got.items():
            numbers[k] = max(numbers[k], v) if k == "max_score_gap" \
                else numbers[k] + v
    correct, checks = judge(numbers)
    log(f"checked_responses: {numbers['checked']}")

    data = RunData(
        cell=cell, device_kind=devices[0].device_kind, setup_s=setup_s,
        window_s=out["window_s"], completed=len(out["done"]),
        latency_s=[lat for *_, lat in out["done"]], spans=spans.spans,
        trace=summary,
        macs={
            name: ops.binary_macs_per_image(reference(cfg).layer_shapes(cfg))
            for name, cfg in cell.tenants.items()
        },
        completed_by={
            name: sum(1 for t, *_ in out["done"] if t == name)
            for name in cell.tenants
        },
    )
    wanted = cell.per_layer if trace else cell.metrics
    metrics = {}
    for name, unit in wanted:
        value = reader(name)(data)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    result = {
        "correct": bool(correct),
        "attempted": len(answers),
        "failed": numbers["failed_requests"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = summary.busy_s if summary else 0.0
        device["window_s"] = summary.window_s if summary else out["window_s"]
        if summary is not None:
            result["breakdown"] = {
                "device_ops": summary.device_ops,
                "idle_gaps": summary.idle_gaps,
            }
    result["plan"] = plans
    result["checks"] = checks
    return result, checks
