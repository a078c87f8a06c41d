"""Delta compilation + content-addressed bank cache (ISSUE 11):

- planner stability: a single added/removed/edited namespace on a
  512-namespace plan moves at most that namespace, the bounded LPT
  rebalance honors its explicit budget, and routing of unchanged
  namespaces is byte-identical;
- DecompCache: replayed decompositions are verdict-identical, the
  cache is guarded by the manifest digest, and host-fallback entries
  replay their oracle;
- bank content keys: deterministic across rebuilds, a one-rule
  constant edit changes exactly the owning shard's key, an instance
  edit invalidates exactly the banks that reference it;
- the persistent-cache directory plumbing: resolve order (explicit →
  env), jax config round-trip, and the mixs flags.
"""
import dataclasses
import os

import numpy as np
import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.cache import DecompCache
from istio_tpu.compiler.layout import InternTable, Tensorizer
from istio_tpu.compiler.ruleset import Rule, compile_ruleset
from istio_tpu.expr.checker import AttributeDescriptorFinder
from istio_tpu.runtime.config import SnapshotBuilder
from istio_tpu.sharding.banks import (bank_content_key,
                                      snapshot_static_digest)
from istio_tpu.sharding.planner import plan_shards
from istio_tpu.testing import workloads
from istio_tpu.testing.workloads import MESH_FINDER, MESH_MANIFEST


def _preds(n: int, n_ns: int) -> list[Rule]:
    return [Rule(name=f"r{i}",
                 match=f'destination.service == "s{i}.cluster"',
                 namespace=f"ns{i % n_ns}")
            for i in range(n)]


# ---------------------------------------------------------------- plan


def test_delta_plan_pure_edit_moves_nothing():
    preds = _preds(1024, 512)
    base = plan_shards(preds, MESH_FINDER, 8)
    edited = list(preds)
    edited[0] = Rule(name="r0",
                     match='destination.service == "other.cluster" '
                           '&& request.method == "GET"',
                     namespace="ns0")
    p2 = plan_shards(edited, MESH_FINDER, 8, prev=base)
    assert p2.stability["mode"] == "delta"
    assert p2.moved_ns == []
    assert p2.ns_to_shard == base.ns_to_shard
    # routing byte-identical, known and unknown namespaces alike
    for ns in list(base.ns_to_shard)[:64] + ["ghost-a", "ghost-b"]:
        assert p2.shard_of(ns) == base.shard_of(ns)


def test_delta_plan_single_add_and_remove():
    preds = _preds(1024, 512)
    base = plan_shards(preds, MESH_FINDER, 8)
    added = preds + [Rule(name="newr", match="connection.mtls",
                          namespace="brand-new-ns")]
    p2 = plan_shards(added, MESH_FINDER, 8, prev=base)
    for ns, k in base.ns_to_shard.items():
        assert p2.ns_to_shard[ns] == k
    assert "brand-new-ns" in p2.ns_to_shard
    assert p2.stability["new"] == 1 and p2.moved_ns == []

    removed = [p for p in preds if p.namespace != "ns5"]
    p3 = plan_shards(removed, MESH_FINDER, 8, prev=base)
    assert "ns5" not in p3.ns_to_shard
    for ns, k in p3.ns_to_shard.items():
        assert base.ns_to_shard[ns] == k
    assert p3.stability["removed"] == 1 and p3.moved_ns == []


def test_delta_plan_rebalance_budget_is_bounded():
    preds = _preds(256, 32)
    base = plan_shards(preds, MESH_FINDER, 4)
    skew = dataclasses.replace(
        base, ns_to_shard={ns: 0 for ns in base.ns_to_shard})
    p0 = plan_shards(preds, MESH_FINDER, 4, prev=skew,
                     rebalance_budget=0)
    assert p0.moved_ns == []      # perfect stability at budget 0
    p3 = plan_shards(preds, MESH_FINDER, 4, prev=skew,
                     rebalance_budget=3)
    assert 0 < len(p3.moved_ns) <= 3
    assert p3.stability["moved"] == p3.moved_ns
    # every move here relocated a previously-placed namespace, and
    # the kept count books exactly those (a relocated FRESH namespace
    # must never be counted as churn — it never sat on a shard)
    assert p3.stability["moved_kept"] == p3.moved_ns
    assert p3.stability["kept"] == \
        len(skew.ns_to_shard) - len(p3.moved_ns)
    assert max(p3.shard_cost) < max(p0.shard_cost)
    # only the moved namespaces changed shard
    drift = {ns for ns in skew.ns_to_shard
             if p3.ns_to_shard[ns] != skew.ns_to_shard[ns]}
    assert drift == set(p3.moved_ns)


def test_delta_plan_shard_width_change_replans_from_scratch():
    preds = _preds(128, 16)
    base = plan_shards(preds, MESH_FINDER, 4)
    p2 = plan_shards(preds, MESH_FINDER, 8, prev=base)
    assert p2.stability.get("mode") != "delta"
    assert p2.n_shards == 8


# -------------------------------------------------------- decomp cache


def test_decomp_cache_replay_is_verdict_identical():
    rules = [Rule(name="a",
                  match='request.method == "GET" || connection.mtls'),
             Rule(name="b",
                  match='destination.service == "x" && '
                        'request.method != "POST"')]
    dc = DecompCache()
    interner = InternTable()
    rs1 = compile_ruleset(rules, MESH_FINDER, interner=interner,
                          decomp_cache=dc)
    assert dc.stats()["misses"] == 2 and dc.stats()["hits"] == 0
    rs2 = compile_ruleset(rules, MESH_FINDER, interner=interner,
                          decomp_cache=dc)
    assert dc.stats()["hits"] == 2
    bags = [bag_from_mapping({"request.method": "GET"}),
            bag_from_mapping({"destination.service": "x",
                              "request.method": "POST",
                              "connection.mtls": False}),
            bag_from_mapping({"connection.mtls": True})]
    ab1 = Tensorizer(rs1.layout, interner).tensorize(bags)
    ab2 = Tensorizer(rs2.layout, interner).tensorize(bags)
    for x, y in zip(rs1(ab1), rs2(ab2)):
        assert (np.asarray(x) == np.asarray(y)).all()


def test_decomp_cache_clears_on_manifest_change():
    dc = DecompCache()
    f1 = AttributeDescriptorFinder({"a": ValueType.BOOL})
    compile_ruleset([Rule(name="r", match="a")], f1, decomp_cache=dc)
    assert dc.stats()["entries"] == 1
    f2 = AttributeDescriptorFinder({"a": ValueType.BOOL,
                                    "b": ValueType.STRING})
    compile_ruleset([Rule(name="r", match="a")], f2, decomp_cache=dc)
    st = dc.stats()
    assert st["entries"] == 1 and st["hits"] == 0 and st["misses"] == 2


def test_decomp_cache_host_fallback_replays_oracle():
    # dnf_cap=1 blows up on the product of sums (the AND distributes
    # to 4 conjunctions) → host fallback, cached
    rules = [Rule(name="blow",
                  match='(connection.mtls || '
                        'request.method == "GET") && '
                        '(destination.service == "x" || '
                        'source.namespace == "y")')]
    dc = DecompCache()
    rs1 = compile_ruleset(rules, MESH_FINDER, dnf_cap=1,
                          decomp_cache=dc)
    assert 0 in rs1.host_fallback
    rs2 = compile_ruleset(rules, MESH_FINDER, dnf_cap=1,
                          decomp_cache=dc)
    assert 0 in rs2.host_fallback
    assert rs2.host_fallback[0] is rs1.host_fallback[0]   # reused
    assert rs2.fallback_reason[0] == rs1.fallback_reason[0]
    bag = bag_from_mapping({"connection.mtls": True,
                            "destination.service": "x"})
    assert rs2.host_eval(0, bag) == (True, False, False)


# ----------------------------------------------------------- bank keys


def _snapshot(store):
    return SnapshotBuilder(MESH_MANIFEST, InternTable()).build(store)


def _keys(snap, plan):
    static = snapshot_static_digest(
        snap, identity_attr="destination.service", buckets=(16,),
        rule_telemetry=False)
    return [bank_content_key(snap, plan, k, static)
            for k in range(plan.n_shards)]


def test_bank_content_keys_deterministic_and_delta_scoped():
    store = workloads.make_fleet_store(240, 8, seed=3)
    s1 = _snapshot(store)
    preds1 = s1.ruleset.rules[:s1.n_config_rules]
    plan1 = plan_shards(preds1, s1.finder, 4)
    keys1 = _keys(s1, plan1)
    assert len(set(keys1)) == 4

    # same store, fresh build → identical plan + keys
    s2 = _snapshot(store)
    preds2 = s2.ruleset.rules[:s2.n_config_rules]
    plan2 = plan_shards(preds2, s2.finder, 4, prev=plan1)
    assert plan2.ns_to_shard == plan1.ns_to_shard
    assert _keys(s2, plan2) == keys1

    # constant-only edit of one rule → exactly its shard's key flips
    key = next(k for k in store.list("rule") if k[1] == "ns1")
    spec = dict(store.get(key))
    spec["match"] = spec["match"].replace('"svc', '"edited-svc', 1)
    store.set(key, spec)
    s3 = _snapshot(store)
    preds3 = s3.ruleset.rules[:s3.n_config_rules]
    plan3 = plan_shards(preds3, s3.finder, 4, prev=plan1)
    keys3 = _keys(s3, plan3)
    changed = [k for k in range(4) if keys3[k] != keys1[k]]
    assert changed == [plan1.shard_of("ns1")]


def test_bank_content_keys_track_instance_edits():
    store = workloads.make_fleet_store(240, 8, seed=3)
    s1 = _snapshot(store)
    plan = plan_shards(s1.ruleset.rules[:s1.n_config_rules],
                       s1.finder, 4)
    keys1 = _keys(s1, plan)
    # the denier's checknothing instance is referenced from every
    # bank (i%3==0 rules everywhere) — editing it must invalidate all
    store.set(("instance", "istio-system", "nothing"),
              {"template": "checknothing", "params": {"x": 1}})
    s2 = _snapshot(store)
    keys2 = _keys(s2, plan)
    assert all(a != b for a, b in zip(keys1, keys2))


# ------------------------------------------------ cache dir round-trip


def test_cache_dir_resolution_and_jax_roundtrip(tmp_path, monkeypatch):
    import jax

    from istio_tpu.compiler import cache as cc

    # precedence: the environment variable wins; else explicit config;
    # else the fixed checkout path
    monkeypatch.setenv(cc.ENV_CACHE_DIR, str(tmp_path / "envdir"))
    assert cc.resolve_cache_dir(None) == str(tmp_path / "envdir")
    assert cc.resolve_cache_dir("/explicit/dir") == \
        str(tmp_path / "envdir")
    monkeypatch.delenv(cc.ENV_CACHE_DIR)
    assert cc.resolve_cache_dir("/explicit/dir") == "/explicit/dir"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.resolve_cache_dir(None) == os.path.join(repo, ".jax_cache")

    # with the variable set, no explicit directory reaches jax.config;
    # the private scope (this test's kind only) clears and restores it
    monkeypatch.setenv(cc.ENV_CACHE_DIR, str(tmp_path / "envdir"))
    try:
        assert cc.configure_persistent_cache("/explicit/dir") == \
            str(tmp_path / "envdir")
        assert jax.config.jax_compilation_cache_dir == \
            str(tmp_path / "envdir")
        with cc.private_cache_dir(str(tmp_path / "cache"),
                                  min_compile_time_s=0.25) as d:
            assert cc.ENV_CACHE_DIR not in os.environ
            assert os.path.isdir(d)
            assert jax.config.jax_compilation_cache_dir == d
            assert jax.config \
                .jax_persistent_cache_min_compile_time_secs == 0.25
            assert cc.persistent_cache_entries(d) == 0
        assert os.environ[cc.ENV_CACHE_DIR] == str(tmp_path / "envdir")
        assert jax.config.jax_compilation_cache_dir == \
            str(tmp_path / "envdir")
    finally:
        monkeypatch.undo()   # the run's own environment, then its cache
        cc.configure_persistent_cache()


def test_mixs_flags_reach_server_args():
    from istio_tpu.cmd.__main__ import build_parser

    args = build_parser().parse_args(
        ["mixs", "--jax-compile-cache-dir", "/tmp/ccc",
         "--shards", "2", "--replicas", "3", "--no-delta-compile",
         "--shard-rebalance-budget", "5"])
    assert args.jax_compile_cache_dir == "/tmp/ccc"
    assert args.shards == 2 and args.replicas == 3
    assert args.no_delta_compile is True
    assert args.shard_rebalance_budget == 5

    from istio_tpu.runtime.server import ServerArgs
    sa = ServerArgs()
    assert sa.delta_compile is True
    assert sa.shard_rebalance_budget == 0
    assert sa.jax_compile_cache_dir is None
