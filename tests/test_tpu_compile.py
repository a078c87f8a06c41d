"""The served Check path's device programs, offered to the TPU compiler
at the north-star width — without a chip.

`jax.experimental.topologies` describes a v5e host that is not
attached; the installed TPU compiler then accepts or refuses each
program exactly as it would on the chip (tiling, memory, code size).
Nothing runs, so these tests say nothing about results or times: they
guard every later PR against a program the chip's compiler refuses or
blows up on, at no chip time. `python chip_smoke.py` is the run.

The topology is described inside a module-scoped fixture — never at
import, in a skipif or in conftest: only the worker that RUNS this
file may load the TPU library. Keep every such compile in this one
file (a second file could land on another xdist worker, whose fixture
would then skip in silence).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

N_RULES = 10_000            # the north-star deployment chip_smoke serves
SHAPES = ((256, 32), (2048, 128))   # (bucket, byte tier) it prewarms
QUOTA_BUCKETS = 100_000
HBM_BYTES = 16 * 1024 ** 3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # conftest turns the persistent cache on; an entry written for a
    # described chip cannot be read back without one (the next compile
    # warns and recompiles) — off around this module's compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plan():
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan
    from istio_tpu.testing import workloads

    snap = SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(
            workloads.make_store(N_RULES))
    return build_fused_plan(snap)


def _on(sharding, tree):
    """Array pytree → ShapeDtypeStructs placed on the described chip
    (there is no device to hold an array)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _step_args(plan, one_chip, bucket, tier):
    eng = plan.engine
    batch = plan.narrow_batch(plan._dummy_batch(bucket, tier))
    ns = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    return (_on(one_chip, eng.params), _on(one_chip, batch), ns,
            _on(one_chip, eng.quota_counts))


def _verdict(plan, one_chip, bucket, tier):
    verdict, _ = jax.eval_shape(plan.engine.raw_step,
                                *_step_args(plan, one_chip, bucket, tier))
    return _on(one_chip, verdict)


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes + m.generated_code_size_in_bytes)
    assert used < HBM_BYTES, m


@pytest.mark.parametrize("bucket,tier", SHAPES)
def test_engine_step_compiles(plan, one_chip, bucket, tier):
    compiled = plan.engine._step.lower(
        *_step_args(plan, one_chip, bucket, tier)).compile()
    _fits(compiled)
    # an unaligned conjunction axis (compiler/ruleset.CONJ_ALIGN) made
    # the compiler unroll the fused gather-compare: 66 MB of code and
    # 130 s at this width, against ~10 MB and ~6 s aligned
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < 32 * 1024 ** 2, code


def test_conjunction_axis_is_aligned(plan):
    from istio_tpu.compiler.ruleset import CONJ_ALIGN

    params = plan.engine.ruleset.params
    assert params["eqc_col"].shape[0] % CONJ_ALIGN == 0
    assert params["lit_idx"].shape[0] % CONJ_ALIGN == 0


@pytest.mark.parametrize("bucket,tier", SHAPES)
def test_check_packer_compiles(plan, one_chip, bucket, tier):
    ns = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    _fits(jax.jit(plan._base_packer()).lower(
        _verdict(plan, one_chip, bucket, tier), ns).compile())


def test_report_packer_compiles(plan, one_chip):
    bucket, tier = SHAPES[-1]
    assert plan.report_lowering is not None and plan.report_rules
    _, batch, ns, _ = _step_args(plan, one_chip, bucket, tier)
    _fits(jax.jit(plan._base_report_packer()).lower(
        _verdict(plan, one_chip, bucket, tier), ns, batch).compile())


def test_rule_telemetry_delta_and_fold_compile(plan, one_chip):
    bucket, tier = SHAPES[-1]
    tele = plan.telemetry
    v = _verdict(plan, one_chip, bucket, tier)
    ns = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    real = jax.ShapeDtypeStruct((bucket,), jnp.bool_, sharding=one_chip)
    _fits(tele._delta_fn.lower(v.matched, v.err, v.status, v.deny_rule,
                               ns, real).compile())
    accs = _on(one_chip, (tele._acc_hit, tele._acc_deny, tele._acc_err))
    _fits(tele._fold_fn.lower(*accs, *accs).compile())


def test_one_check_program_compiles(plan, one_chip):
    """FusedPlan._base_step: step + rule-telemetry delta and fold +
    packer as the one program packed_check launches a batch, at the
    largest served shape."""
    bucket, tier = SHAPES[-1]
    tele = plan.telemetry
    assert plan.mesh is None and tele is not None
    n_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    accs = _on(one_chip, (tele._acc_hit, tele._acc_deny, tele._acc_err))
    compiled = jax.jit(plan._base_step()).lower(
        *_step_args(plan, one_chip, bucket, tier), n_real, *accs).compile()
    _fits(compiled)
    # the CONJ_ALIGN guard of test_engine_step_compiles holds for the
    # composed program too
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < 32 * 1024 ** 2, code


@pytest.fixture(scope="module")
def route_plan():
    """benchmark/configs/routematch10k.json as run: 10 000 match
    blocks, each its own regex (BASELINE config 3)."""
    import importlib.util
    import json
    from pathlib import Path

    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan

    configs = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
    sizes = json.loads((configs / "routematch10k.json").read_text())
    spec = importlib.util.spec_from_file_location(
        "bench_config_routematch", configs / "routematch.py")
    config = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(config)
    snap = SnapshotBuilder(default_manifest={
        k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]}).build(
            config.make_store(sizes))
    return build_fused_plan(snap)


def test_candidate_tier_step_compiles_at_10k_automata(route_plan,
                                                      one_chip):
    """Both banks of the route table past the one-hot tiers, scanned
    a host's own blocks a row (8 + 3); the whole-bank gather this replaces put
    the bank in the program text (625 MB of StableHLO a shape, 335 MB
    of code) and gathered [2048, 7500] a byte."""
    plan = route_plan
    banks = plan.engine.ruleset.geometry["dfa_banks"]
    assert [(b["tier"], b["automata"], b["candidates"]) for b in banks] \
        == [("candidates", 7500, 8), ("candidates", 2500, 3)]
    assert sum(b["bytes"] for b in banks) < 64 * 1024 ** 2
    bucket, tier = SHAPES[-1]
    tele = plan.telemetry
    n_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    accs = _on(one_chip, (tele._acc_hit, tele._acc_deny, tele._acc_err))
    lowered = jax.jit(plan._base_step()).lower(
        *_step_args(plan, one_chip, bucket, tier), n_real, *accs)
    # the banks ride the arguments, not the program
    assert len(lowered.as_text()) < 4 * 1024 ** 2
    compiled = lowered.compile()
    _fits(compiled)
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < 32 * 1024 ** 2, code


def test_wide_step_compiles_at_10k_automata(route_plan, one_chip):
    """`step_wide` (FusedPlan._base_step(wide=True)): the route table's
    program over the wide byte plane, at the one shape the length split
    launches it in (256 rows x 2 048 bytes)."""
    plan = route_plan
    bucket, width = plan.all_warm_shapes((64, 256, 2048))[-1]
    assert (bucket, width) == (256, plan.wide_width) and width == 2048
    tele = plan.telemetry
    n_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    accs = _on(one_chip, (tele._acc_hit, tele._acc_deny, tele._acc_err))
    step = plan._base_step(wide=True)
    assert step.__name__ == "step_wide"       # the profiler's name
    lowered = jax.jit(step).lower(
        *_step_args(plan, one_chip, bucket, width), n_real, *accs)
    assert len(lowered.as_text()) < 4 * 1024 ** 2
    compiled = lowered.compile()
    _fits(compiled)
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < 32 * 1024 ** 2, code


@pytest.mark.parametrize("variant", ("fast", "unit", "seg"))
def test_rolling_quota_alloc_compiles(one_chip, variant):
    """The three alloc kernels DeviceQuotaPool._flush selects between,
    at a 100k-key pool and the pool's large pad shape."""
    from istio_tpu.models.quota_alloc import make_rolling_alloc_step
    from istio_tpu.runtime.device_quota import _TICKS_PER_WINDOW

    _scan, fast, unit, seg = make_rolling_alloc_step(
        QUOTA_BUCKETS, _TICKS_PER_WINDOW)
    step = {"fast": fast, "unit": unit, "seg": seg}[variant]
    n = 512

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, b = jnp.int32, jnp.bool_
    _fits(step.lower(arr(i32, QUOTA_BUCKETS, _TICKS_PER_WINDOW),
                     arr(i32, n), arr(i32, n), arr(b, n), arr(i32, n),
                     arr(b, n), arr(i32, n), arr(i32, n),
                     arr(b, n)).compile())
