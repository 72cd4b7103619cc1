"""The process-wide compile counters (core/telemetry.py) see JAX's own
compile events: a fresh jit is one trace, one lowering, one backend
compile and one persistent-cache miss, its second call none; a second
process with the same cache loads it, a hit with retrieval seconds.
Each process is a subprocess, so the cache starts empty and on."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    import numpy as np
    from repro.core import telemetry as T

    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    f = jax.jit(lambda x: x * 3 + 1)
    x = np.arange(16, dtype=np.int32)
    a = T.compile_counters()
    np.asarray(f(x))
    b = T.compile_counters()
    np.asarray(f(x))
    c = T.compile_counters()
    print(json.dumps([T.compile_delta(a, b), T.compile_delta(b, c)]))
""")

# the first snapshot is taken after the compile: the counters run from
# the module's import, not from the first snapshot
SINCE_IMPORT = textwrap.dedent("""
    import json, sys
    import jax
    import numpy as np
    from repro.core import telemetry as T

    np.asarray(jax.jit(lambda x: x - 7)(np.arange(4, dtype=np.int32)))
    print(json.dumps([T.compile_counters()]))
""")


def run(cache_dir, script: str = SCRIPT) -> list:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", script, str(cache_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_counters_see_misses_hits_and_stages(tmp_path):
    first, again = run(tmp_path)
    assert first["trace_n"] >= 1 and first["lower_n"] == 1
    assert first["backend_compile_n"] == 1
    assert first["cache_misses"] == 1 and first["cache_hits"] == 0
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["backend_compile_s"] > 0
    assert all(v == 0 for v in again.values()), again

    loaded, again = run(tmp_path)
    assert loaded["cache_hits"] == 1 and loaded["cache_misses"] == 0
    assert loaded["cache_retrievals"] == 1
    assert loaded["cache_retrieval_s"] > 0
    assert loaded["backend_compile_n"] == 1
    # the backend-compile span holds the cache lookup
    assert loaded["backend_compile_s"] >= loaded["cache_retrieval_s"]
    assert all(v == 0 for v in again.values()), again


def test_compile_counters_count_from_the_import(tmp_path):
    work, = run(tmp_path, SINCE_IMPORT)
    assert work["backend_compile_n"] >= 1 and work["lower_n"] >= 1
    assert work["trace_s"] > 0 and work["backend_compile_s"] > 0


def test_covered_folds_nested_and_overlapping_spans():
    from repro.core.telemetry import _covered
    assert _covered([]) == 0
    assert _covered([(0, 10), (2, 3), (5, 12), (20, 21)]) == 13
