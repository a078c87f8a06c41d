"""Roofline smoke: the CI gate for the roofline accounting layer
(compiler/roofline.py — ISSUE 6).

Three contracts pinned, FAIL (nonzero exit) on any breach:

1. SECTION KEYS — `roofline.bench_fields` emits
   `<prefix>fraction_of_roof` and a
   named `<prefix>bound` in {hbm, mxu, host} for the headline-,
   rbac-, full-mesh- and capacity-shaped engines. If a section's
   roofline ever silently degrades to its `*_roofline_error`
   fallback, CI catches it here, not in the next perf round.
2. EXACT BYTES — the model's prediction matches the COMPILED shapes
   exactly where exactness is well-defined: `h2d_batch` equals a real
   tensorized AttributeBatch's summed nbytes, `d2h_packed` equals a
   real packed_check pull's nbytes, and the index-tensor bytes inside
   the match components equal the live `RuleSetProgram.params`
   arrays' nbytes. No hand constants.
3. INTROSPECT — /debug/roofline serves the same model per serving
   bucket over real HTTP.

Runnable under JAX_PLATFORMS=cpu; tier-1 invokes main() in-process
(tests/test_roofline_smoke.py).

Usage: JAX_PLATFORMS=cpu python scripts/roofline_smoke.py [--rules N]
"""
import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BOUNDS = ("hbm", "mxu", "host")


def _check_fields(failures: list, fields: dict, prefix: str) -> None:
    frac = fields.get(prefix + "fraction_of_roof")
    bound = fields.get(prefix + "bound")
    if not isinstance(frac, (int, float)) or not 0 <= frac <= 1:
        failures.append(
            f"{prefix}fraction_of_roof missing/out of range: {frac!r}"
            f" (error field: "
            f"{fields.get(prefix + 'roofline_error')!r})")
    if bound not in BOUNDS:
        failures.append(f"{prefix}bound missing/unnamed: {bound!r}")
    for key in ("bytes_per_step", "achieved_gbps", "roof_platform"):
        if prefix + key not in fields:
            failures.append(f"{prefix}{key} missing")


def main(n_rules: int = 64) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from istio_tpu.compiler import roofline
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan
    from istio_tpu.testing import workloads

    failures: list[str] = []
    batch = 64

    # ---- 1. roofline fields for each engine family ----
    engines = {}
    engines["headline_"] = workloads.make_engine(
        n_rules=n_rules, with_quota=True, jit=False)
    # capacity section: same engine family, no quota
    engines["capacity_"] = workloads.make_engine(
        n_rules=n_rules, with_quota=False, jit=False)
    snap = SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(
        workloads.make_rbac_store(8))
    engines["rbac_"] = build_fused_plan(snap).engine
    engines["full_mesh_"] = workloads.make_full_mesh(
        n_services=16, n_roles=4)[0]
    for prefix, engine in engines.items():
        fields = roofline.bench_fields(engine, batch, 1e-3, prefix)
        _check_fields(failures, fields, prefix)

    # ---- 2. bytes-per-step prediction matches compiled shapes ----
    engine = engines["headline_"]
    model = roofline.model_check_step(engine, batch)
    bags = workloads.make_bags(batch)
    ab = engine.tensorizer.tensorize(bags)
    actual_h2d = sum(int(np.asarray(a).nbytes) for a in (
        ab.ids, ab.present, ab.map_present, ab.str_bytes, ab.str_lens,
        ab.hash_ids))
    got = model.component("h2d_batch").bytes
    if got != actual_h2d:
        failures.append(f"h2d_batch model {got} != tensorized batch "
                        f"nbytes {actual_h2d}")
    # index-tensor bytes == the live device params' nbytes
    params = engine.ruleset.params
    g = engine.ruleset.geometry
    if g["n_fused_conjs"]:
        want = sum(int(np.asarray(params[k]).nbytes) for k in
                   ("eqc_col", "eqc_cid", "eqc_xor", "eqc_pad"))
        got = model.component("match_fused_eq").bytes \
            - batch * g["n_fused_conjs"] * (g["l_max_fused"] * 5 + 1)
        if got != want:
            failures.append(f"match_fused_eq index bytes {got} != "
                            f"params nbytes {want}")
    want = sum(int(np.asarray(params[k]).nbytes) for k in
               ("conj_m_idx", "conj_n_idx"))
    got = model.component("match_rules").bytes \
        - batch * g["n_rows"] * (2 * g["k_max"] + 3)
    if got != want:
        failures.append(f"match_rules index bytes {got} != params "
                        f"nbytes {want}")

    # d2h_packed == a real packed pull's nbytes (serving plan)
    store = workloads.make_store(max(n_rules // 2, 8))
    splan = build_fused_plan(SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(store))
    smodel = roofline.model_check_step(splan.engine, batch,
                                       plan=splan)
    sbatch = splan.engine.tensorizer.tensorize(
        workloads.make_bags(batch))
    packed = splan.packed_check(sbatch, np.zeros(batch, np.int32),
                                observe=False)
    got = smodel.component("d2h_packed").bytes
    if got != int(packed.nbytes):
        failures.append(f"d2h_packed model {got} != packed pull "
                        f"nbytes {int(packed.nbytes)}")
    if roofline.packed_pull_rows(splan) != packed.shape[0]:
        failures.append(
            f"packed_pull_rows {roofline.packed_pull_rows(splan)} != "
            f"pull rows {packed.shape[0]}")

    # ---- 3. /debug/roofline over real HTTP ----
    from istio_tpu.introspect import IntrospectServer
    from istio_tpu.runtime import RuntimeServer, ServerArgs

    srv = RuntimeServer(store, ServerArgs(
        batch_window_s=0.0005, max_batch=64, buckets=(16, 64),
        default_manifest=workloads.MESH_MANIFEST))
    intro = IntrospectServer(runtime=srv)
    try:
        port = intro.start()
        srv.check_many(workloads.make_bags(8))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/roofline",
                timeout=10) as resp:
            payload = json.loads(resp.read())
        if "buckets" not in payload or "64" not in payload["buckets"]:
            failures.append(
                f"/debug/roofline missing bucket models: "
                f"{sorted(payload)}")
        else:
            entry = payload["buckets"]["64"]
            if entry.get("bytes_per_step", 0) <= 0:
                failures.append("/debug/roofline bucket 64 has no "
                                "bytes_per_step")
    finally:
        intro.close()
        srv.close()

    if failures:
        print("ROOFLINE SMOKE FAILED:")
        for f in failures:
            print(" -", f)
        return 1
    print(f"roofline smoke ok: {len(engines)} sections keyed, exact "
          f"h2d/d2h/index bytes, /debug/roofline live")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rules", type=int, default=64)
    args = ap.parse_args()
    sys.exit(main(n_rules=args.rules))
