"""Each configuration's plain reference against the program's host
oracle, at the configuration's smoke sizes."""
import json
from pathlib import Path

import pytest

import run

ROOT = Path(__file__).resolve().parent.parent.parent
M = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_reference_agrees_with_host_oracle(entry):
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
    from istio_tpu.runtime import RuntimeServer, ServerArgs

    sizes = json.loads((ROOT / entry["file"]).read_text())
    sizes.update(sizes["smoke"])
    config = run.load_module(
        ROOT / "benchmark" / "configs" / f"{sizes['module']}.py")
    requests = config.make_requests(sizes, 1024, seed=4000000011)
    expected = [config.reference(sizes)(d) for d in requests]
    srv = RuntimeServer(config.make_store(sizes), ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]},
        buckets=tuple(sizes["buckets"]), max_batch=sizes["max_batch"],
        initial_prewarm=False))
    try:
        oracle = run.statuses(srv.controller.dispatcher.check_host_oracle(
            [bag_from_mapping(d) for d in requests]))
    finally:
        srv.close()
    assert expected == oracle
    assert len(set(expected)) > 1, "one-sided: the check shows nothing"
