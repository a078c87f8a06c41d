"""Device-side byte-string predicates over padded uint8 tensors.

Strings that participate in glob/regex/prefix/suffix predicates are
materialized as fixed-width ``uint8[B, L]`` rows plus ``int32[B]`` lengths
(SURVEY.md §7 "hard parts #1"). Everything here is jit-compatible and
shape-static; XLA fuses the comparisons into neighbouring ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def pad_bytes(values: list[bytes], max_len: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side: pack python byte strings into [N, L] uint8 + [N] int32."""
    out = np.zeros((len(values), max_len), dtype=np.uint8)
    lens = np.zeros(len(values), dtype=np.int32)
    for i, v in enumerate(values):
        trunc = v[:max_len]
        out[i, :len(trunc)] = np.frombuffer(trunc, dtype=np.uint8)
        lens[i] = len(trunc)
    return out, lens


def prefix_match(data: jnp.ndarray, lens: jnp.ndarray,
                 prefix: bytes) -> jnp.ndarray:
    """startsWith(const): [B, L] × pattern → bool [B]."""
    p = np.frombuffer(prefix, dtype=np.uint8)
    k = len(p)
    if k == 0:
        return jnp.ones(data.shape[0], dtype=bool)
    if k > data.shape[1]:
        return jnp.zeros(data.shape[0], dtype=bool)
    eq = jnp.all(data[:, :k] == jnp.asarray(p), axis=-1)
    return eq & (lens >= k)


def prefix_match_many(data: jnp.ndarray, lens: jnp.ndarray,
                      prefixes: list[bytes]) -> jnp.ndarray:
    """startsWith for k constants at once: [B, L] → bool [B, k]; column
    i is prefix_match(data, lens, prefixes[i]). One [B, k] compare per
    byte position of the longest prefix, where k prefix_match calls are
    k slices, compares and reductions to trace, lower and compile."""
    plen = np.array([len(p) for p in prefixes], np.int32)
    width = min(int(plen.max(initial=0)), data.shape[1])
    table = np.zeros((len(prefixes), width), np.uint8)
    for i, p in enumerate(prefixes):
        head = np.frombuffer(p[:width], dtype=np.uint8)
        table[i, :len(head)] = head
    # a prefix wider than the plane matches no row (prefix_match)
    hit = (lens[:, None] >= plen[None, :]) \
        & jnp.asarray(plen <= data.shape[1])[None, :]
    for j in range(width):
        same = data[:, j, None] == table[None, :, j]
        hit = hit & (same | jnp.asarray(plen <= j)[None, :])
    return hit


def suffix_match(data: jnp.ndarray, lens: jnp.ndarray,
                 suffix: bytes) -> jnp.ndarray:
    """endsWith(const): compare a window ending at each row's length."""
    p = np.frombuffer(suffix, dtype=np.uint8)
    k = len(p)
    b, l = data.shape
    if k == 0:
        return jnp.ones(b, dtype=bool)
    if k > l:
        return jnp.zeros(b, dtype=bool)
    # gather indices len-k .. len-1 per row (clipped; masked by lens >= k)
    offs = jnp.arange(k, dtype=jnp.int32)[None, :] + (lens[:, None] - k)
    offs = jnp.clip(offs, 0, l - 1)
    window = jnp.take_along_axis(data, offs, axis=1)
    return jnp.all(window == jnp.asarray(p), axis=-1) & (lens >= k)


def exact_match(data: jnp.ndarray, lens: jnp.ndarray,
                pattern: bytes) -> jnp.ndarray:
    p = np.frombuffer(pattern, dtype=np.uint8)
    k = len(p)
    if k > data.shape[1]:
        return jnp.zeros(data.shape[0], dtype=bool)
    padded = np.zeros(data.shape[1], dtype=np.uint8)
    padded[:k] = p
    return jnp.all(data == jnp.asarray(padded), axis=-1) & (lens == k)


def glob_match(data: jnp.ndarray, lens: jnp.ndarray,
               pattern: str) -> jnp.ndarray:
    """The `match()` extern with a constant pattern
    (externs.go:108-116): trailing '*' = prefix, leading '*' = suffix,
    else exact."""
    pb = pattern.encode()
    if pb.endswith(b"*"):
        return prefix_match(data, lens, pb[:-1])
    if pb.startswith(b"*"):
        return suffix_match(data, lens, pb[1:])
    return exact_match(data, lens, pb)


def dyn_prefix_match(s_data, s_lens, p_data, p_lens) -> jnp.ndarray:
    """startsWith with a RUNTIME prefix: both sides are byte planes.
    [B, L] × [B, L] → bool [B]."""
    l = s_data.shape[1]
    pos = jnp.arange(l, dtype=jnp.int32)[None, :]
    eq = (s_data == p_data) | (pos >= p_lens[:, None])
    return jnp.all(eq, axis=1) & (s_lens >= p_lens)


def dyn_suffix_match(s_data, s_lens, p_data, p_lens,
                     p_shift: int = 0) -> jnp.ndarray:
    """endsWith with a RUNTIME suffix: compare s's last (p_len - shift)
    bytes against p[shift:] (shift=1 serves `*x` globs)."""
    l = s_data.shape[1]
    k = p_lens - p_shift                       # effective suffix length
    pos = jnp.arange(l, dtype=jnp.int32)[None, :]
    offs = jnp.clip(pos + (s_lens - k)[:, None], 0, l - 1)
    window = jnp.take_along_axis(s_data, offs, axis=1)
    if p_shift:
        p_cmp = jnp.roll(p_data, -p_shift, axis=1)
    else:
        p_cmp = p_data
    eq = (window == p_cmp) | (pos >= k[:, None])
    return jnp.all(eq, axis=1) & (s_lens >= k) & (k >= 0)


def dyn_exact_match(s_data, s_lens, p_data, p_lens) -> jnp.ndarray:
    eq = jnp.all(s_data == p_data, axis=1)
    return eq & (s_lens == p_lens)


def dyn_glob_match(s_data, s_lens, p_data, p_lens) -> jnp.ndarray:
    """match() with a RUNTIME pattern (externs.go:108-116 semantics):
    trailing '*' = prefix of p[:-1], leading '*' = suffix of p[1:],
    else exact. The '*' probes read the pattern's first/last bytes
    per row; all three candidate verdicts are computed and selected."""
    l = s_data.shape[1]
    star = np.uint8(ord("*"))
    last = jnp.take_along_axis(
        p_data, jnp.clip(p_lens - 1, 0, l - 1)[:, None], axis=1)[:, 0]
    trailing = (p_lens > 0) & (last == star)
    leading = (p_lens > 0) & (p_data[:, 0] == star)
    prefix = dyn_prefix_match(s_data, s_lens, p_data,
                              jnp.maximum(p_lens - 1, 0))
    suffix = dyn_suffix_match(s_data, s_lens, p_data, p_lens,
                              p_shift=1)
    exact = dyn_exact_match(s_data, s_lens, p_data, p_lens)
    return jnp.where(trailing, prefix,
                     jnp.where(leading, suffix, exact))


def lex_cmp(a_data: jnp.ndarray, a_lens: jnp.ndarray,
            b_data: jnp.ndarray, b_lens: jnp.ndarray) -> jnp.ndarray:
    """Row-wise lexicographic comparison of two padded byte planes →
    int32 [B] in {-1, 0, 1} (sign of a ⋛ b).

    Padding is zero, so when one row is a strict prefix of the other
    the first differing position reads 0 vs the longer row's next byte
    — the correct "shorter sorts first" verdict — except when the
    longer row's byte IS 0 (embedded NUL), which the length tiebreak
    below also resolves. Numeric order keys are fixed 8-byte rows, so
    for them every path is exact. Ordered comparisons (expr LSS/LEQ/
    GTR/GEQ, reference func.go) lower here over the SAME planes the
    string predicates use; truncation handling lives in the caller
    (tensor_expr._compile_cmp)."""
    diff = a_data != b_data                       # [B, L]
    has = jnp.any(diff, axis=1)
    first = jnp.argmax(diff, axis=1)
    av = jnp.take_along_axis(a_data, first[:, None], axis=1)[:, 0]
    bv = jnp.take_along_axis(b_data, first[:, None], axis=1)[:, 0]
    byte_cmp = jnp.sign(av.astype(jnp.int32) - bv.astype(jnp.int32))
    len_cmp = jnp.sign(a_lens - b_lens).astype(jnp.int32)
    return jnp.where(has, byte_cmp, len_cmp)


def pack_bits(a: np.ndarray) -> np.ndarray:
    """Host-side bit packing of a bool/0-1 array along its LAST axis →
    uint32 lanes, little-endian bit order within each 32-bit word,
    width ceil(n/32). THE storage format for every bit-packed bank /
    mask weight (one-hot DFA step matrices in regex_dfa; attr/instance
    literal masks in the engine + packer): a one-hot transition bank
    stored as f32 was 32× the HBM-resident bytes of its information
    content. `unpack_bits` below is the on-device inverse."""
    a = np.ascontiguousarray(np.asarray(a) != 0)
    n = a.shape[-1]
    w = max((n + 31) // 32, 0)
    padded = np.zeros(a.shape[:-1] + (w * 32,), bool)
    padded[..., :n] = a
    packed8 = np.ascontiguousarray(
        np.packbits(padded, axis=-1, bitorder="little"))
    return packed8.view(np.uint32)


def unpack_bits(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """On-device inverse of pack_bits: uint32 bit lanes
    [..., W] → bool [..., n] (little-endian within each word). The
    unpack is elementwise VPU work that runs ONCE per kernel
    invocation; the packed lanes are what lives in HBM (and what the
    compiled program carries), so a bank's resident weight is 1/32 of
    its f32 one-hot formulation."""
    bits = (packed[..., None] >> jnp.arange(32, dtype=jnp.uint32)) \
        & jnp.uint32(1)
    flat = bits.reshape(packed.shape[:-1] + (-1,))
    return flat[..., :n] != 0


def dfa_match(data: jnp.ndarray, lens: jnp.ndarray,
              transitions: jnp.ndarray, accept: jnp.ndarray) -> jnp.ndarray:
    """Run one dense DFA over every row: state := T[state, byte] for the
    first `len` bytes, then read the accept bit.

    data [B, L] uint8, transitions [S, 256] int32, accept [S] bool.
    Implemented as a lax.scan over the L byte positions (time-major
    transpose) — each step is one [B] gather from the flattened table.
    """
    b, l = data.shape
    flat = transitions.reshape(-1)  # [S*256]
    bytes_tm = data.T  # [L, B]
    # data-dependent trip count: strings are typically far shorter than
    # the slot width, and every position ≥ max(lens) is a frozen no-op
    # — a while_loop stops at the batch's longest string instead of
    # paying the full L scan-step latencies
    maxlen = jnp.minimum(jnp.max(lens), l)

    def cond(carry):
        i, _ = carry
        return i < maxlen

    def body(carry):
        i, state = carry
        byte = jax.lax.dynamic_index_in_dim(bytes_tm, i, 0,
                                            keepdims=False)
        nxt = flat[state * 256 + byte.astype(jnp.int32)]
        state = jnp.where(i < lens, nxt, state)
        return i + 1, state

    _, final = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros(b, dtype=jnp.int32)))
    return accept[final]


def dfa_match_many(data: jnp.ndarray, lens: jnp.ndarray,
                   trans_bank: jnp.ndarray,
                   accept_bank: jnp.ndarray) -> jnp.ndarray:
    """Vectorized multi-pattern DFA: run N packed DFAs (pack_dfas) over the
    same subject rows in ONE scan.

    data [B, L], trans_bank [N, S, 256], accept_bank [N, S] →  bool [B, N].

    All N automata are renumbered into ONE global state space (state of
    pattern i lives at i·S + s), so each scan step is a single 1-D
    gather of [B, N] next-states from a flat [(N·S)·256] table — the
    same efficient gather shape as the single-DFA case. (A vmap over
    per-pattern dfa_match compiled to a batched gather XLA:TPU executes
    ~16× slower — 58 ms vs 3.6 ms for 11 patterns × 256 bytes.)
    """
    n, s, a = trans_bank.shape
    offsets = jnp.arange(n, dtype=jnp.int32) * s           # [N]
    flat = (trans_bank.astype(jnp.int32)
            + offsets[:, None, None]).reshape(-1)          # [(N·S)·A]
    accept_flat = accept_bank.reshape(-1)                  # [N·S]
    b, l = data.shape

    bytes_tm = data.T                                      # [L, B]
    maxlen = jnp.minimum(jnp.max(lens), l)

    def cond(carry):
        i, _ = carry
        return i < maxlen

    def body(carry):
        i, state = carry
        byte = jax.lax.dynamic_index_in_dim(bytes_tm, i, 0,
                                            keepdims=False)
        nxt = flat[state * a + byte[:, None].astype(jnp.int32)]
        state = jnp.where((i < lens)[:, None], nxt, state)
        return i + 1, state

    init = jnp.broadcast_to(offsets[None, :], (b, n))
    _, final = jax.lax.while_loop(cond, body, (jnp.int32(0), init))
    return accept_flat[final]


def dfa_match_candidates(data: jnp.ndarray, lens: jnp.ndarray,
                         cand: jnp.ndarray, local: jnp.ndarray,
                         accept: jnp.ndarray, class_of: jnp.ndarray,
                         s_max: int, width: int) -> jnp.ndarray:
    """N packed DFAs, each row against its OWN K of them
    (regex_dfa.pack_dfas_candidates): `cand` int32 [B, K] names them,
    the bank's dead automaton N where a row has fewer.

    No gather runs inside the scan, nor before it. The rows' own
    tables are fetched ONCE, K contiguous rows of `local` a request,
    and laid [K, S·W, B], the batch on the lane axis; a byte step is
    then one masked reduce over the S·W axis (cell state·W + class of
    each (k, row)), which the vector unit does at full width: 4-8 µs a
    byte in the served step. XLA:TPU's gather costs ~50 µs an
    operation plus ~6 ns an element: a [K, B] gather a byte was 153 µs
    (2048 x 8), the byte → class lookup through a 256-entry table
    1.4 ms, and the whole-bank scan this replaces, [2048, 7500] a
    byte, 7.2 s a batch (PERF.md §6, PR 33). Bytes are mapped to the
    bank's byte classes once, before the loop, by a compare against
    each of the 256 byte values.

    → bool [B, K]: acceptance of the row's k-th candidate."""
    l = data.shape[1]
    cand = jnp.asarray(cand)
    mine = jnp.transpose(jnp.asarray(local)[cand], (1, 2, 0))   # [K, S·W, B]
    byte = jnp.arange(256, dtype=jnp.int32)[:, None, None]
    cls_tm = jnp.sum(jnp.where(data.T.astype(jnp.int32)[None] == byte,
                               jnp.asarray(class_of)[:, None, None], 0),
                     axis=0)                                    # [L, B]
    cell = jnp.arange(s_max * width, dtype=jnp.int32)[None, :, None]
    maxlen = jnp.minimum(jnp.max(lens), l)

    def cond(carry):
        i, _ = carry
        return i < maxlen

    def body(carry):
        i, state = carry                                   # [K, B], local
        cls = jax.lax.dynamic_index_in_dim(cls_tm, i, 0, keepdims=False)
        at = (state * width + cls[None, :])[:, None, :]
        nxt = jnp.sum(jnp.where(cell == at, mine, 0).astype(jnp.int32),
                      axis=1)
        state = jnp.where((i < lens)[None, :], nxt, state)
        return i + 1, state

    _, final = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros(cand.T.shape, jnp.int32)))
    return jnp.asarray(accept)[cand.T * s_max + final].T


def dfa_match_many_onehot(data: jnp.ndarray, lens: jnp.ndarray,
                          packed: dict) -> jnp.ndarray:
    """Multi-pattern DFA on the MXU: states ride as ONE-HOT vectors and
    each byte step is a matmul, not a gather (`packed` from
    regex_dfa.pack_dfas_onehot).

    Per step: class one-hot [B, C] from a byte compare + cls matmul,
    outer-product with the state one-hot u [B, S] → [B, S·C], then
    × step-matrix [S·C, S] → next one-hot. All values are exact 0/1 so
    bf16 accumulation is lossless. XLA:TPU executes the raw per-step
    [B, N] table gather at ~0.5 GB/s effective (58 ms for 11 patterns ×
    256 bytes); this formulation runs the same automata in ~2 ms.

    → bool [B, N] acceptance per pattern.
    """
    b, l = data.shape
    s_tot, n_cls = packed["n_states"], packed["n_classes"]
    # bit-packed bank → bf16 once per invocation (unpack-on-device)
    step_m = unpack_bits(jnp.asarray(packed["step_bits"]),
                         s_tot).astype(jnp.bfloat16)
    cls_m = jnp.asarray(packed["cls"], jnp.bfloat16)
    accept = jnp.asarray(packed["accept"], jnp.bfloat16)
    starts = packed["starts"]

    u0 = np.zeros((1, s_tot), np.float32)
    u0[0, starts] = 1.0   # one-hot start of every pattern, summed —
    # patterns never share states, so the N automata advance
    # independently inside one vector
    u0 = jnp.broadcast_to(jnp.asarray(u0, jnp.bfloat16), (b, s_tot))

    bytes_tm = data.T
    maxlen = jnp.minimum(jnp.max(lens), l)

    def cond(carry):
        i, _ = carry
        return i < maxlen

    def body(carry):
        i, u = carry
        byte = jax.lax.dynamic_index_in_dim(bytes_tm, i, 0,
                                            keepdims=False)
        onehot256 = (byte[:, None] ==
                     jnp.arange(256, dtype=byte.dtype)[None, :]
                     ).astype(jnp.bfloat16)
        c1 = onehot256 @ cls_m                     # [B, C]
        v = (u[:, :, None] * c1[:, None, :]).reshape(b, s_tot * n_cls)
        nxt = v @ step_m                           # [B, S]
        u = jnp.where((i < lens)[:, None], nxt, u)
        return i + 1, u

    _, final = jax.lax.while_loop(cond, body, (jnp.int32(0), u0))
    return (final @ accept) > 0.5


def dfa_match_many_onehot_blocked(data: jnp.ndarray, lens: jnp.ndarray,
                                  packed: dict) -> jnp.ndarray:
    """Block-diagonal MXU DFA bank (regex_dfa.pack_dfas_onehot_blocked):
    per-pattern one-hot states [B, N, s_max] advanced by a batched
    matmul over the pattern axis. Per-step flops are O(B·N·s_max²·C) —
    linear in the bank where the dense formulation is quadratic — so
    banks of many small automata (glob groups) ride the MXU instead of
    the latency-bound gather scan.

    → bool [B, N] acceptance per pattern."""
    b, l = data.shape
    s_max, n_cls = packed["n_states_max"], packed["n_classes"]
    n = packed["n_pats"]
    # bit-packed blocks → bf16 once per invocation [N, s·C, s]
    step_m = unpack_bits(jnp.asarray(packed["step_bits"]),
                         s_max).astype(jnp.bfloat16)
    cls_m = jnp.asarray(packed["cls"], jnp.bfloat16)     # [256, C]
    accept = jnp.asarray(packed["accept"], jnp.bfloat16)  # [N, s]

    u0 = np.zeros((1, n, s_max), np.float32)
    u0[0, :, 0] = 1.0          # every pattern starts in local state 0
    u0 = jnp.broadcast_to(jnp.asarray(u0, jnp.bfloat16), (b, n, s_max))

    bytes_tm = data.T
    maxlen = jnp.minimum(jnp.max(lens), l)

    def cond(carry):
        i, _ = carry
        return i < maxlen

    def body(carry):
        i, u = carry
        byte = jax.lax.dynamic_index_in_dim(bytes_tm, i, 0,
                                            keepdims=False)
        onehot256 = (byte[:, None] ==
                     jnp.arange(256, dtype=byte.dtype)[None, :]
                     ).astype(jnp.bfloat16)
        c1 = onehot256 @ cls_m                        # [B, C]
        v = (u[:, :, :, None] * c1[:, None, None, :]
             ).reshape(b, n, s_max * n_cls)
        nxt = jnp.einsum("bnk,nks->bns", v, step_m,
                         preferred_element_type=jnp.bfloat16)
        u = jnp.where((i < lens)[:, None, None], nxt, u)
        return i + 1, u

    _, final = jax.lax.while_loop(cond, body, (jnp.int32(0), u0))
    return jnp.einsum("bns,ns->bn", final, accept,
                      preferred_element_type=jnp.float32) > 0.5
