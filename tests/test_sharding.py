"""Sharding plan unit tests (no devices needed for spec logic) +
multi-device integration via a subprocess (so the main test process
keeps seeing exactly 1 device)."""

import subprocess
import sys
import textwrap

import pytest

from repro import configs as C
from repro.core.hep_shard import ShardTrial, search
from repro.parallel.sharding import ShardScheme, default_scheme


def test_default_scheme_size_adaptive():
    assert default_scheme(C.get("qwen2_0_5b")).tp is False   # <2B: DP only
    assert default_scheme(C.get("mamba2_130m")).tp is False
    s14 = default_scheme(C.get("qwen2_5_14b"))
    assert s14.tp is True and s14.fsdp == "zero1"
    sg = default_scheme(C.get("grok_1_314b"))
    assert sg.tp is True and sg.fsdp == "zero3"              # >20B: ZeRO-3


def test_expert_mode_auto():
    ds = C.get("deepseek_moe_16b")
    assert ShardScheme().resolve_expert_mode(ds, 16) == "ep"   # 64 % 16
    gk = C.get("grok_1_314b")
    assert ShardScheme().resolve_expert_mode(gk, 16) == "tp"   # 8 % 16


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs as C
    from repro.models.transformer import init_params, forward
    from repro.parallel.sharding import make_param_shardings, make_batch_shardings, ShardScheme
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = C.get_smoke("olmo_1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    scheme = ShardScheme(tp=True, fsdp="zero1")
    p_sh = make_param_shardings(cfg, mesh, params, scheme)
    params_s = jax.tree.map(jax.device_put, params, p_sh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    toks_s = jax.device_put(toks, NamedSharding(mesh, P("data", None)))

    with mesh:
        sharded = jax.jit(lambda p, t: forward(cfg, p, t)[0])(params_s, toks_s)
    local = forward(cfg, params, toks)[0]
    err = float(jnp.max(jnp.abs(sharded.astype(jnp.float32) - local.astype(jnp.float32))))
    rel = err / (float(jnp.max(jnp.abs(local))) + 1e-9)
    assert rel < 2e-4, f"sharded != local: rel {rel}"
    print("SHARDED-OK", rel)
""")


def test_sharded_forward_matches_local():
    """8-device SPMD forward == single-device forward (subprocess)."""
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        timeout=600,
    )
    assert "SHARDED-OK" in r.stdout, r.stdout + r.stderr


# ------------------------- HEP-Shard search -------------------------------


def test_hep_shard_search_finds_planted_optimum():
    """Coordinate descent reaches the planted best scheme and never
    returns a worse-cost scheme than any it evaluated."""
    target = ShardScheme(tp=False, fsdp="zero3", batch_over_model=True)

    def evaluate(s: ShardScheme) -> ShardTrial:
        dist = (
            (s.tp != target.tp)
            + (s.fsdp != target.fsdp)
            + (s.batch_over_model != target.batch_over_model)
        )
        return ShardTrial(
            scheme=s, compute_s=0.1 + dist, memory_s=0.05,
            collective_s=0.01 * dist, peak_bytes=2**30,
        )

    best, history = search(
        evaluate,
        knobs={
            "tp": (True, False),
            "fsdp": ("zero1", "zero3"),
            "batch_over_model": (False, True),
        },
        log=None,
    )
    assert best.scheme.tp == target.tp
    assert best.scheme.fsdp == target.fsdp
    assert best.scheme.batch_over_model == target.batch_over_model
    assert best.cost == min(t.cost for t in history)


def test_hep_shard_oom_penalty_dominates():
    def evaluate(s: ShardScheme) -> ShardTrial:
        fits = s.fsdp == "zero3"
        return ShardTrial(
            scheme=s,
            compute_s=1.0 if fits else 0.1,   # the OOM config is "faster"
            memory_s=0.0, collective_s=0.0,
            peak_bytes=2**30 if fits else 64 * 2**30,
        )

    best, _ = search(
        evaluate, knobs={"fsdp": ("zero1", "zero3")}, log=None
    )
    assert best.scheme.fsdp == "zero3"  # fitting beats fast-but-OOM

def test_hep_shard_transfer_split_in_cost():
    """h2d/d2h staging is priced separately from the on-device step and
    can flip the argmin toward a transfer-lighter scheme."""
    t = ShardTrial(
        scheme=ShardScheme(), compute_s=1.0, memory_s=0.5,
        collective_s=0.1, peak_bytes=2**30, h2d_s=0.2, d2h_s=0.05,
    )
    assert t.kernel_s == pytest.approx(1.1)
    assert t.transfer_s == pytest.approx(0.25)
    assert t.cost == pytest.approx(1.35)

    def evaluate(s: ShardScheme) -> ShardTrial:
        heavy = s.fsdp == "zero1"  # faster kernel, much heavier staging
        return ShardTrial(
            scheme=s, compute_s=0.1 if heavy else 0.12,
            memory_s=0.0, collective_s=0.0, peak_bytes=2**30,
            h2d_s=0.5 if heavy else 0.0, d2h_s=0.0,
        )

    best, _ = search(
        evaluate, knobs={"fsdp": ("zero1", "zero3")}, log=None
    )
    assert best.scheme.fsdp == "zero3"


def test_hep_shard_all_failing_knob_skipped():
    """A knob whose every candidate value fails evaluation must be
    skipped, not crash the search with min() on an empty list."""
    def evaluate(s: ShardScheme) -> ShardTrial:
        if s.tp:
            raise RuntimeError("tp unsupported on this mesh")
        return ShardTrial(
            scheme=s, compute_s=1.0, memory_s=0.0,
            collective_s=0.0, peak_bytes=2**30,
        )

    best, _ = search(
        evaluate, ShardScheme(tp=False), knobs={"tp": (True,)}, log=None
    )
    assert best.scheme.tp is False
