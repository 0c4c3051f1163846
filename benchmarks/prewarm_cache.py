"""Pre-warm the JAX persistent compilation cache.

    PYTHONPATH=src python -m benchmarks.prewarm_cache [cache_dir]

The cache is the one ``repro.launch.compile_cache.enable_compile_cache``
selects: ``cache_dir`` when given (it becomes
``JAX_COMPILATION_CACHE_DIR``), else ``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``.  CI passes the test suite's directory
(``.pytest_cache/jax_persistent_cache``, which ``tests/conftest.py``
uses unless ``JAX_COMPILATION_CACHE_DIR`` is set), restores it via
``actions/cache`` (keyed on JAX version + kernel-source hash) and runs
this script on a cache miss, so the first test run of a fresh key
already loads compiled executables from disk.

Compiles the batch-evaluator kernels the suite leans on hardest: the
default paper topology plus every registered arch, on the common
(ndims=3, bucket=16) signature, both uniform and structured density
modes, broadcast and stacked variants, at the canonical padded batch
shapes.
"""
from __future__ import annotations

import os
import sys
from typing import Optional


def main(cache_dir: Optional[str] = None) -> None:
    if cache_dir:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import numpy as np

    from repro.configs.paper_workloads import (banded_attention_workloads,
                                               by_name)
    from repro.core import jax_cost, search
    from repro.core.arch import registered_archs

    rng = np.random.default_rng(0)
    wls = [by_name("mm1"), by_name("mm3")]
    archs = ["cloud"] + sorted(registered_archs())
    for arch in archs:
        for wl in wls:
            spec, ev = search.get_evaluator(wl, arch, n_pad=16)
            ev(spec.random_genomes(rng, 64))
        specs_evs = [search.get_evaluator(wl, arch, n_pad=16)
                     for wl in wls]
        jax_cost.eval_stacked(
            [ev for _, ev in specs_evs],
            [spec.random_genomes(rng, 64) for spec, _ in specs_evs])
    # structured-density kernels (the mixed fleet of the sweep guard)
    swls = [by_name("mm1"), banded_attention_workloads()[0]]
    models, batches = [], []
    for wl in swls:
        spec, ev = search.get_evaluator(wl, "cloud", n_pad=32,
                                        structured=True)
        g = spec.random_genomes(rng, 64)
        ev(g)
        models.append(ev)
        batches.append(g)
    jax_cost.eval_stacked(models, batches)
    print(f"prewarmed {jax_cost.compilation_count()} compilations into "
          f"{cache_dir}")


if __name__ == "__main__":
    main(*sys.argv[1:2])
