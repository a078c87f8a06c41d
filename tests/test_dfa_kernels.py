"""DFA device-kernel parity: all three evaluation tiers (flat gather,
dense one-hot MXU, block-diagonal one-hot MXU) must agree with the
host automaton on mixed pattern banks — the blocked tier is only
reachable past the dense size gate in production, so it needs direct
coverage (r4 review finding)."""
import numpy as np

from istio_tpu.ops import bytes_ops
from istio_tpu.ops.regex_dfa import (compile_regex, dfa_matches_host,
                                     pack_dfas, pack_dfas_classes,
                                     pack_dfas_onehot,
                                     pack_dfas_onehot_blocked)

PATS = ([f"^/api/v{k}/" for k in range(6)] +
        [r"items/[0-9]+", r"^/x$", r"a+b*c", r"(foo|bar)baz"])
SUBJECTS = [b"/api/v3/items/77", b"/x", b"/xx", b"", b"aac", b"abc",
            b"ac", b"/items/123", b"zzz", b"/api/v9/x", b"foobaz",
            b"xbarbazy"]


def _tensors():
    L = 32
    data = np.zeros((len(SUBJECTS), L), np.uint8)
    lens = np.zeros(len(SUBJECTS), np.int32)
    for i, s in enumerate(SUBJECTS):
        data[i, :len(s)] = np.frombuffer(s, np.uint8)
        lens[i] = len(s)
    return data, lens


def test_all_dfa_tiers_match_host_oracle():
    dfas = [compile_regex(p) for p in PATS]
    data, lens = _tensors()
    want = np.asarray([[dfa_matches_host(d, s) for d in dfas]
                       for s in SUBJECTS])

    trans, accept = pack_dfas(dfas)
    gather = np.asarray(bytes_ops.dfa_match_many(data, lens, trans,
                                                 accept))
    np.testing.assert_array_equal(gather, want)

    classes = pack_dfas_classes(dfas)
    dense = np.asarray(bytes_ops.dfa_match_many_onehot(
        data, lens, pack_dfas_onehot(dfas, classes)))
    np.testing.assert_array_equal(dense, want)

    blocked = np.asarray(bytes_ops.dfa_match_many_onehot_blocked(
        data, lens, pack_dfas_onehot_blocked(dfas, classes)))
    np.testing.assert_array_equal(blocked, want)


PREFIXES = [b"", b"/", b"/api/v3/", b"/api/v9/x", b"/x", b"/xx", b"aac",
            b"foobazz", b"z" * 32, b"/api/v3/items/77" + b"0" * 17]


def test_prefix_group_columns_are_the_single_prefix_match():
    # an empty prefix, one as long as its subject, one as wide as the
    # plane and one wider
    data, lens = _tensors()
    assert max(map(len, PREFIXES)) > data.shape[1]
    many = np.asarray(bytes_ops.prefix_match_many(data, lens, PREFIXES))
    want = np.asarray([[s.startswith(p) for p in PREFIXES]
                       for s in SUBJECTS])
    np.testing.assert_array_equal(many, want)
    for i, p in enumerate(PREFIXES):
        np.testing.assert_array_equal(
            many[:, i], np.asarray(bytes_ops.prefix_match(data, lens, p)))
    assert many.sum() > len(SUBJECTS)      # more than the empty prefix
    # a narrowed plane (fused.narrow_batch) keeps the true lengths
    short = lens <= 8
    narrow = np.asarray(bytes_ops.prefix_match_many(
        data[short, :8], lens[short], PREFIXES))
    np.testing.assert_array_equal(narrow, want[short])
    assert 0 < short.sum() < len(SUBJECTS)
