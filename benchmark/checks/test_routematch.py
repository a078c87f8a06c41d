"""The `routematch10k` configuration's own pieces: its plain reference
on hand-written cases of each family, the manifest's new entries, the
roofline count on a worked example, and the `dfa` scope on a step
compiled from the smoke store."""
import json
import re
from pathlib import Path

import pytest

import run
import scopes

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = ROOT / "benchmark"
CELL = "routematch10k-check-deep"
READERS = ("device_dfa_ms", "dfa_bank_mb", "dfa_candidates_per_row",
           "setup_dfa_build_s", "dfa_roofline_share")


@pytest.fixture(scope="module")
def smoke():
    sizes = json.loads((BENCH / "configs" / "routematch10k.json").read_text())
    sizes.update(sizes["smoke"])
    return sizes, run.load_module(BENCH / "configs" / "routematch.py")


def _request(sizes, config, s, path, **headers):
    return {"destination.service": config.host_of(sizes, s),
            "source.namespace": "ns1", "request.method": "GET",
            "request.path": path, "request.headers": headers}


def _block(sizes, config, family, deny, hosts=None):
    """The first block of the family that denies, or one that carries
    the no-op check alone, among all hosts or the given ones."""
    return next(r for r in range(sizes["rules"])
                if config.family_of(sizes, r) == family
                and (r % sizes["deny_every"] == 0) == deny
                and r % sizes["whitelist_every"] != 1
                and (hosts is None or r % sizes["services"] in hosts))


# (family, deny?) -> request line / headers that match block r, the
# status a matching denier or no-op gives, and a near miss
@pytest.mark.parametrize("family, hit, miss", [
    (0, lambda r: (f"/api/v{r % 9}/r{r}/{{res}}/77", {}),
     lambda r: (f"/api/v{r % 9}/r{r}/{{res}}/77x", {})),
    (1, lambda r: (f"/r{r}/reviews/12345/v{r % 4}", {}),
     lambda r: (f"/r{r}/ratings/12345/v{r % 4}", {})),
    (2, lambda r: ("/static/1", {"cookie": f"a=b;user=group{r};t=d"}),
     lambda r: ("/static/1", {"cookie": f"a=b; user=group{r};t=d"})),
    (3, lambda r: (f"/v{r % 3}/t/acme-7/r{r}/x/y",
                   {"x-version": f"v{r % 5}"}),
     lambda r: (f"/v{r % 3}/t/acme-7/r{r}/x/y",
                {"x-version": f"v{(r + 1) % 5}"})),
])
def test_reference_on_hand_written_cases(smoke, family, hit, miss):
    sizes, config = smoke
    expected = config.reference(sizes)
    r = _block(sizes, config, family, deny=True)
    s = r % sizes["services"]
    res = config.RESOURCES[r % len(config.RESOURCES)]
    for make, want in ((hit, config.DENIED), (miss, 0)):
        path, headers = make(r)
        assert expected(_request(sizes, config, s, path.format(res=res),
                                 **headers)) == want
    # the same request line addressed to another host: no block of it
    path, headers = hit(r)
    assert expected(_request(sizes, config, s + 1, path.format(res=res),
                             **headers)) == 0
    # a block that only carries the no-op check answers OK
    quiet = _block(sizes, config, family, deny=False)
    path, headers = hit(quiet)
    res = config.RESOURCES[quiet % len(config.RESOURCES)]
    assert expected(_request(sizes, config, quiet % sizes["services"],
                             path.format(res=res), **headers)) == 0


def test_reference_takes_the_lower_rule_of_two_and_the_whitelist(smoke):
    sizes, config = smoke
    expected = config.reference(sizes)
    listed = next(r for r in range(sizes["rules"])
                  if r % sizes["whitelist_every"] == 1 and r % 3
                  and config.family_of(sizes, r) == 1)
    s = listed % sizes["services"]
    path = f"/r{listed}/products/1/v{listed % 4}"
    inside = _request(sizes, config, s, path)
    assert "ns1" not in config.WHITELIST and "ns2" in config.WHITELIST
    assert expected(inside) == config.NOT_FOUND
    assert expected({**inside, "source.namespace": "ns2"}) == 0
    # two blocks of one host match, a denier and a no-op: the request
    # is denied whichever comes first; a whitelist miss before a
    # denier stands (the first status that is not OK)
    full = json.loads((BENCH / "configs" / "routematch10k.json").read_text())
    expected = config.reference(full)
    uri = next(r for r in range(full["rules"])
               if r % full["whitelist_every"] == 1 and r % 3
               and config.family_of(full, r) == 1)
    host = {uri % full["services"]}
    later = next(r for r in range(uri + 1, full["rules"])
                 if r % full["services"] in host and r % 3 == 0
                 and config.family_of(full, r) == 2)
    both = _request(full, config, uri % full["services"],
                    f"/r{uri}/products/1/v{uri % 4}",
                    cookie=f"user=group{later}")
    assert expected(both) == config.NOT_FOUND
    assert expected({**both, "source.namespace": "ns2"}) == config.DENIED


def test_blocks_are_lowered_by_pilots_own_match_to_predicate(smoke):
    from istio_tpu.pilot.route_nfa import match_to_predicate

    sizes, config = smoke
    spec = config.rule_specs(sizes)[_block(sizes, config, 3, deny=False)]
    rule = config.make_store(sizes).get(
        ("rule", spec["namespace"], spec["name"]))
    assert rule["match"] == match_to_predicate(spec["host"], spec["match"])
    assert rule["match"].startswith(
        f'destination.service == "{spec["host"]}" && "^(/v')
    assert 'request.headers["x-version"]' in rule["match"]


def test_new_manifest_entries_resolve():
    cell = run.resolve_cell(CELL, smoke=False)
    assert cell.chips == 1 and cell.sizes["rules"] == 10000
    assert cell.sizes["services"] == 1000 and cell.mix["depth"] == 4096
    assert cell.sizes["quota_name"] is None
    assert cell.sizes["reduced"] == ["route_selection"]
    assert set(cell.sizes["guarantees"]) == set(json.loads(
        (BENCH / "configs" / "mixer10k.json").read_text())["guarantees"])
    names = [m["name"] for m in cell.per_layer]
    assert set(READERS) <= set(names)
    # it runs the wire front under the deep mix and the `lists` section
    assert {"wire_p99_ms.deep", "device_lists_ms"} <= set(names)
    assert [m["name"] for m in cell.end_to_end] == ["check_rate", "setup_s"]
    for other in ("mixer10k-check-deep", "fullmesh5k-check-deep"):
        assert not set(READERS) & {
            m["name"] for m in run.resolve_cell(other, False).per_layer}
    for name in READERS:
        assert callable(run.load_module(
            BENCH / "layer_metrics" / f"{name}.py").read)


def test_roofline_count_on_a_worked_example():
    dfa = run.load_module(BENCH / "rooflines" / "dfa.py")
    # 1000 rows; a host holds 7.5 blocks on a 40-byte path and 2.5 on
    # a cookie that is 60 bytes when averaged over rows without one
    subjects = [(7.5, 40.0), (2.5, 60.0)]
    want = 1000 * ((7.5 * 40 * 4 + 40) + (2.5 * 60 * 4 + 60))
    assert dfa.scan_bytes(1000, subjects) == want == 1_900_000
    # moved in 1 ms on a v5e: 1.9e6 / 819e9 s of work = 0.232 %
    share = dfa.roofline_share_pct(1000, subjects, 1.0, "TPU v5 lite")
    assert share == pytest.approx(100 * 1.9e6 / 819e9 / 1e-3)
    assert 0.2 < share < 0.25
    with pytest.raises(KeyError, match="no memory bandwidth"):
        dfa.roofline_share_pct(1000, subjects, 1.0, "cpu")


def test_roofline_reader_counts_from_the_generators_data(smoke):
    sizes, config = smoke
    reader = run.load_module(BENCH / "layer_metrics" /
                             "dfa_roofline_share.py")
    (on_path, path), (on_cookie, cookie) = reader.subjects_of(sizes, config)
    assert on_path + on_cookie == sizes["rules"] / sizes["services"] == 10
    assert on_path == 7.5 and on_cookie == 2.5
    assert 24 <= path <= 72 and 0.6 * 48 <= cookie <= 112


def test_roofline_reader_takes_the_sizes_of_the_served_snapshot():
    reader = run.load_module(BENCH / "layer_metrics" /
                             "dfa_roofline_share.py")
    full, small = reader.served_sizes(10000), reader.served_sizes(300)
    assert (full["services"], small["services"]) == (1000, 30)
    assert small["max_batch"] == 256 and full["max_batch"] == 2048
    assert reader.served_sizes(10001) is None     # no such deployment


def test_a_step_compiled_from_the_smoke_store_has_operations_under_dfa(
        smoke):
    import jax
    import jax.numpy as jnp

    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan

    sizes, config = smoke
    plan = build_fused_plan(SnapshotBuilder(default_manifest={
        k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]}).build(
            config.make_store(sizes)))
    eng = plan.engine
    bucket = sizes["buckets"][0]
    batch = plan.narrow_batch(plan._dummy_batch(bucket, 128))
    # lowered afresh: an executable out of a compile cache keeps the
    # metadata it was compiled with (the cache's key leaves it out)
    hlo = jax.jit(eng.raw_step).lower(
        eng.params, batch, jnp.zeros((bucket,), jnp.int32),
        eng.quota_counts).compile().as_text()
    ops = [(name, 0, 1) for name in re.findall(r'op_name="([^"]*)"', hlo)]
    assert scopes.scope_seconds(ops, "dfa") is not None
    assert scopes.scope_seconds(ops, "match") is not None
    under = [name for name, _, _ in ops if "dfa" in name.split("/")]
    assert any("match" in name.split("/") and "while" in name.split("/")
               for name in under)      # the scan itself, inside `match`
