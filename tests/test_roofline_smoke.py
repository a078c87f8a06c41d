"""Tier-1 hook for scripts/roofline_smoke.py: the CI gate that
`roofline.bench_fields` (`*_fraction_of_roof`, a named `*_bound`)
stays emitted for each engine family and that the model's bytes-per-step
prediction matches the compiled shapes exactly (h2d batch planes,
d2h packed pull, index-tensor params). Runs main() in-process."""
import importlib.util
import os
import sys


def test_roofline_smoke_main():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "roofline_smoke.py")
    spec = importlib.util.spec_from_file_location(
        "roofline_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
        rc = mod.main(n_rules=32)
    finally:
        sys.modules.pop(spec.name, None)
    assert rc == 0
