"""Mesh construction + sharding rules for the batched policy step.

Sharding layout for the RuleSetProgram gather pipeline
(compiler/ruleset.py) with axes ("dp", "mp"):

    lit        [B, 2A+1]   → P("dp")        batch over dp, atoms replicated
    lit_idx    [n_conj, L] replicated
    sat        [B, n_conj] → P("dp")
    conj_*_idx [R, K]      → P("mp")        rules over mp
    matched    [B, R]      → P("dp", "mp")

Sharding RULES (an un-contracted output dim) over "mp" keeps the request
path collective-free: each mp shard owns a rule slice end-to-end. The
final per-request verdict fold (deny/allow over rules) contracts the
sharded R axis, so XLA inserts exactly one small psum over "mp" — the
only ICI traffic per step. Batch stays on "dp" throughout.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """dp × mp factorization of the available devices."""
    dp: int
    mp: int = 1

    def build(self, devices: Sequence[Any] | None = None) -> Mesh:
        devs = list(devices if devices is not None else jax.devices())
        need = self.dp * self.mp
        if len(devs) < need:
            raise ValueError(f"need {need} devices, have {len(devs)}")
        arr = np.asarray(devs[:need]).reshape(self.dp, self.mp)
        return Mesh(arr, axis_names=("dp", "mp"))


def policy_mesh(n_devices: int | None = None, rule_shards: int = 1) -> Mesh:
    """Default mesh: dp × mp with `rule_shards` cores on the rule axis."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n % rule_shards:
        raise ValueError(f"{n} devices not divisible by mp={rule_shards}")
    return MeshSpec(dp=n // rule_shards, mp=rule_shards).build()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Prefix sharding for an AttributeBatch pytree: leading batch dim on
    dp, everything else replicated."""
    return NamedSharding(mesh, P("dp"))


def shard_batch(mesh: Mesh, batch) -> Any:
    """Place an AttributeBatch pytree with its batch dim over dp."""
    sh = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)


def param_shardings(mesh: Mesh, engine) -> dict:
    """THE param-sharding policy for the dp/mp layout: every non-rule-
    axis param (lit_idx, the fused gather-compare eqc_* tensors, any
    future addition) replicates; only the [R, K] conjunction matrices
    shard their rule axis over mp. One home — shard_engine_check and
    mesh_stage_probe must agree or the probe's jit fails with a
    sharding/pytree mismatch when the param set changes."""
    rep = NamedSharding(mesh, P())
    mp_rules = NamedSharding(mesh, P("mp"))
    param_shard = {k: rep for k in engine.params}
    param_shard["conj_m_idx"] = mp_rules
    param_shard["conj_n_idx"] = mp_rules
    return param_shard


def mesh_stage_probe(mesh: Mesh, engine, batch, req_ns,
                     steps: int = 3, reps: int = 2) -> dict:
    """Per-stage timers for the sharded check step (read by
    `__graft_entry__.dryrun_multichip`): on a 1-core host the
    end-to-end scaling ratio is time-slicing noise, but the STAGES
    still attribute where the sharding machinery spends —

      shard_dispatch_ms    host→device placement of the batch under
                           the dp sharding (per step)
      match_ms             the ruleset match program alone, outputs
                           left dp×mp-sharded: collective-FREE (each
                           mp shard owns its rule slice end-to-end)
      full_step_ms         match + verdict fold; the fold contracts
                           the sharded rule axis, so XLA inserts the
                           step's only psum over mp here
      fold_collectives_ms  full − match: the verdict fold plus every
                           collective it forces

    Returns median-of-reps wall times per chained step."""
    import time

    dp = NamedSharding(mesh, P("dp"))
    dpmp = NamedSharding(mesh, P("dp", "mp"))
    rep = NamedSharding(mesh, P())
    match_fn = jax.jit(lambda p, b: engine.ruleset.fn(p, b),
                       in_shardings=(param_shardings(mesh, engine), dp),
                       out_shardings=(dpmp, dpmp, dpmp))
    full_fn = shard_engine_check(mesh, engine)

    def med(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    # shard dispatch: the per-step host→device placement cost
    disp = []
    for _ in range(reps + 1):
        t0 = time.perf_counter()
        for _ in range(steps):
            placed = shard_batch(mesh, batch)
            jax.block_until_ready(placed)
        disp.append((time.perf_counter() - t0) / steps)
    placed = shard_batch(mesh, batch)
    ns = jax.device_put(np.asarray(req_ns), dp)
    counts = jax.device_put(np.asarray(engine.quota_counts), rep)

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(*args)
            jax.block_until_ready(out)
            ts.append((time.perf_counter() - t0) / steps)
        return med(ts)

    t_match = timed(match_fn, engine.params, placed)
    t_full = timed(full_fn, engine.params, placed, ns, counts)
    return {
        "shard_dispatch_ms": round(med(disp[1:]) * 1e3, 3),
        "match_ms": round(t_match * 1e3, 3),
        "full_step_ms": round(t_full * 1e3, 3),
        "fold_collectives_ms": round(max(t_full - t_match, 0.0) * 1e3,
                                     3),
    }


def shard_engine_check(mesh: Mesh, engine) -> Callable:
    """jit a PolicyEngine.raw_step under the dp/mp layout.

    batch + req_ns shard over dp; quota counters replicate (each dp
    replica is a best-effort quota domain, exactly the reference's
    per-replica memquota stance); matched/err verdict planes + the
    rule-dimension params (RM/RN columns) shard rules over mp. Returns
    fn(params, batch, req_ns, quota_counts) → (CheckVerdict, counts)."""
    from istio_tpu.models.policy_engine import CheckVerdict
    dp = NamedSharding(mesh, P("dp"))
    dpmp = NamedSharding(mesh, P("dp", "mp"))
    rep = NamedSharding(mesh, P())
    param_shard = param_shardings(mesh, engine)
    out_verdict = CheckVerdict(status=dp, valid_duration_s=dp,
                               valid_use_count=dp, referenced=dp,
                               matched=dpmp, err=dpmp, deny_rule=dp,
                               err_count=rep)
    return jax.jit(engine.raw_step,
                   in_shardings=(param_shard, dp, dp, rep),
                   out_shardings=(out_verdict, rep))
