"""Test configuration.

Puts JAX on a virtual 8-device CPU platform so multi-chip sharding
paths (Mesh/pjit/shard_map) are exercised hermetically. The chip is
reached only through `python chip_smoke.py` and `benchmark/run.py`,
never from the tests.

Also turns on JAX's persistent compilation cache where
compiler/cache.resolve_cache_dir says (JAX_COMPILATION_CACHE_DIR when
set, else the repo-local `.jax_cache/` — the same directory
chip_smoke.py uses; entries are keyed by HLO + platform, so sharing
is safe). The suite builds near-identical engines in dozens of modules
— each fresh Engine re-traces the same programs, and without the disk
cache every one is a full XLA compile. With it, duplicate compiles are
disk hits both within one run and across runs. Tests that assert on
cache behavior (test_delta_compile, delta_smoke) take a private
directory through compiler/cache.private_cache_dir.
"""
from istio_tpu.platform import force_cpu_platform

force_cpu_platform(8)

from istio_tpu.compiler.cache import configure_persistent_cache

configure_persistent_cache()
