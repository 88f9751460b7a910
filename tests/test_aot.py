"""Persistent compile cache + AOT executables (VERDICT round-1 item 8)."""

import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from netsdb_tpu.plan import aot
from netsdb_tpu.relational.queries import (COLUMNAR_QUERIES,
                                           compile_suite,
                                           tables_from_rows)
from netsdb_tpu.workloads import tpch


@pytest.fixture(scope="module")
def tables():
    return tables_from_rows(tpch.generate(scale=2, seed=13))


def test_export_round_trip_simple():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: a @ b + 1.0)
    x = jnp.ones((8, 8))
    blob = aot.export_jitted(fn, x, x)
    call = aot.load_exported(blob)
    np.testing.assert_allclose(np.asarray(call(x, x)),
                               np.asarray(fn(x, x)))


def test_tpch_suite_export_and_reload(tables, tmp_path):
    path = str(tmp_path / "suite.bin")
    aot.export_tpch_suite(tables, path)
    assert os.path.getsize(path) > 0
    loaded = aot.load_tpch_suite(path, tables)
    got = loaded()
    want = compile_suite(tables)()
    import jax

    flat_g, _ = jax.tree_util.tree_flatten(got)
    flat_w, _ = jax.tree_util.tree_flatten(want)
    assert len(flat_g) == len(flat_w)
    for g, w in zip(flat_g, flat_w):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-4)


def test_suite_load_refuses_incompatible_tables(tables, tmp_path):
    """The exported program bakes data-dependent statics (dict codes,
    key spaces, join plans); loading against tables with different
    statics must fail loudly, not silently compute wrong answers."""
    path = str(tmp_path / "suite.bin")
    aot.export_tpch_suite(tables, path)
    other = tables_from_rows(tpch.generate(scale=3, seed=99))
    with pytest.raises(ValueError, match="different static"):
        aot.load_tpch_suite(path, other)


def test_ff_export_round_trip(tmp_path):
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as g

    fn, args = g.entry()
    path = str(tmp_path / "ff.bin")
    aot.save_exported(path, jax.jit(fn), *args)
    call = aot.load_exported(path)
    got = call(*args)
    want = jax.jit(fn)(*args)
    gf, _ = jax.tree_util.tree_flatten(got)
    wf, _ = jax.tree_util.tree_flatten(want)
    for a, b in zip(gf, wf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


_TWO_CLIENTS = """
import sys
import jax
import jax.numpy as jnp
from netsdb_tpu.client import Client
from netsdb_tpu.config import Configuration
for root in sys.argv[1:]:
    Client(Configuration(root_dir=root))
out = jax.jit(lambda x: (x @ x.T).sum())(jnp.ones((64, 64)))
assert float(out) == 64.0 ** 3
print(jax.config.jax_compilation_cache_dir)
"""


def _run_two_clients(tmp_path, env):
    roots = [str(tmp_path / "r1"), str(tmp_path / "r2")]
    proc = subprocess.run(
        [sys.executable, "-c", _TWO_CLIENTS] + roots, check=True, env=env,
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for root in roots:
        for _dir, subdirs, _files in os.walk(root):
            assert "compile_cache" not in subdirs, _dir
    return proc.stdout.strip().splitlines()[-1]


def test_compilation_cache_follows_env(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, jax's own reading of it
    stands: two Clients with different roots leave the directory where
    the variable says, entries land there (a second process can reuse
    them — the PreCompiledWorkload behavior), and no per-root cache
    directory appears."""
    cache = str(tmp_path / "cc")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    assert _run_two_clients(tmp_path, env) == cache
    assert os.listdir(cache), "compilation cache is empty after a jit"


def test_compilation_cache_fixed_path_when_unset(tmp_path):
    """Unset, the cache is the one fixed git-ignored directory inside
    the checkout, whatever the Clients' roots."""
    from netsdb_tpu.config import COMPILE_CACHE_DIR

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert _run_two_clients(tmp_path, env) == COMPILE_CACHE_DIR
    assert os.listdir(COMPILE_CACHE_DIR)
