"""The scope reduction on hand-made operations."""
import pytest

import scopes

MS = 10 ** 9    # a trace counts in ps
OPS = [
    ("jit(step)/lists/eq:", 0, 2 * MS),
    # nested in the one above: the same 2 ms, not 1 more
    ("jit(step)/lists/jit(_take)/gather:", MS, 2 * MS),
    ("jit(step)/lists/reduce_or:", 5 * MS, 6 * MS),
    ("jit(step)/rbac/reduce_or:", 7 * MS, 7 * MS + MS // 2),
    ("jit(step)/match/playlists/eq:", 8 * MS, 9 * MS),
    (None, 9 * MS, 10 * MS),
]


def test_an_operation_counts_under_a_scope_that_is_a_whole_component():
    assert scopes.scope_seconds(OPS, "lists") == 3e-3
    assert scopes.scope_seconds(OPS, "rbac") == 0.5e-3
    assert scopes.scope_seconds(OPS, "playlists") == 1e-3
    assert scopes.scope_seconds(OPS, "quota") is None
    assert scopes.scope_seconds(OPS, "list") is None


def test_a_trace_without_steps_or_without_the_scope_reads_nothing(
        monkeypatch):
    for ops, steps, want in ((OPS, 4, 0.75), (OPS, 0, None), ((), 4, None)):
        monkeypatch.setattr(scopes, "load_step_ops",
                            lambda path, r=(tuple(ops), steps): r)
        assert scopes.scope_ms_per_step("x", "lists") == want
    monkeypatch.setattr(scopes, "load_step_ops", lambda path: (OPS, 4))
    assert scopes.scope_ms_per_step("x", "quota") is None


def _write_trace(path, ops) -> str:
    """A hand-made xplane file: two step programs and a packer on the
    device's `XLA Modules` line, `ops` on its `XLA Ops` line, the scope
    path as a string stat or a reference of each operation's metadata;
    a host plane that is skipped."""
    space = scopes._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.lines.add(name="python3").events.add(metadata_id=1,
                                              duration_ps=5)
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "hlo_category"), (3, OPS[2][0])):
        plane.stat_metadata[key].name = name
    modules = plane.lines.add(name="XLA Modules")
    for key, name in ((50, "jit_step(123)"), (51, "jit_pack(4)")):
        plane.event_metadata[key].name = name
    for key in (50, 51, 50):
        modules.events.add(metadata_id=key, duration_ps=MS)
    line = plane.lines.add(name="XLA Ops")
    for key, (op_path, start, end) in enumerate(ops, start=100):
        meta = plane.event_metadata[key]
        meta.name = f"%fusion.{key}"
        meta.stats.add(metadata_id=2, str_value="loop fusion")
        if op_path == OPS[2][0]:      # by reference
            meta.stats.add(metadata_id=1, ref_value=3)
        elif op_path is not None:
            meta.stats.add(metadata_id=1, str_value=op_path)
        line.events.add(metadata_id=key, offset_ps=start,
                        duration_ps=end - start)
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_the_scope_path_is_read_from_the_events_metadata(tmp_path):
    path = _write_trace(tmp_path / "hand.xplane.pb", OPS)
    assert scopes.load_step_ops(path) == (tuple(OPS), 2)
    assert scopes.scope_ms_per_step(path, "lists") == 1.5
    assert scopes.scope_ms_per_step(path, "rbac") == 0.25
    assert scopes.scope_ms_per_step(path, "match") == 0.5
    assert scopes.scope_ms_per_step(path, "quota") is None


def test_a_step_that_carries_no_scope_is_an_error_not_a_none(tmp_path):
    # an executable from a cache written before the scopes: its
    # operations have paths, none under `match`
    stale = [(op_path and op_path.replace("/match/", "/").replace(
        "/lists/", "/").replace("/rbac/", "/"), s, e)
        for op_path, s, e in OPS]
    path = _write_trace(tmp_path / "stale.xplane.pb", stale)
    assert scopes.load_step_ops(path)[1] == 2
    for scope in ("lists", "rbac", "match"):
        with pytest.raises(RuntimeError, match="compile cache"):
            scopes.scope_ms_per_step(path, scope)
