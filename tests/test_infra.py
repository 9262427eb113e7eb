"""Infra-layer tests (reference parity: test_validation / test_jax_compat /
test_has_cuda / flush, SURVEY.md §2.6)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mpi4jax_tpu as m4j
from mpi4jax_tpu.utils import config, dtypes, jax_compat, validation


def test_config_truthiness():
    assert config.parse_bool("1") and config.parse_bool("TRUE")
    assert not config.parse_bool("0") and not config.parse_bool("off")
    with pytest.raises(ValueError):
        config.parse_bool("maybe", name="X")


def test_flag_env(monkeypatch):
    monkeypatch.setenv("MPI4JAX_TPU_DEBUG", "yes")
    assert config.debug_enabled()
    monkeypatch.setenv("MPI4JAX_TPU_DEBUG", "0")
    assert not config.debug_enabled()


def test_dtype_wire_codes_unique_and_supported():
    codes = [dtypes.wire_code(d) for d in dtypes.SUPPORTED_DTYPES]
    assert len(set(codes)) == len(codes)
    assert dtypes.wire_code(jnp.bfloat16) == 10  # native/tpucomm.h contract
    with pytest.raises(TypeError):
        dtypes.wire_code(np.dtype("datetime64[s]"))


def test_validation_static_int():
    assert validation.check_static_int("root", np.int64(3)) == 3
    with pytest.raises(TypeError, match="integer"):
        validation.check_static_int("root", 1.5)
    with pytest.raises(TypeError, match="bool"):
        validation.check_static_int("root", True)


def test_validation_range():
    with pytest.raises(TypeError, match="out of range"):
        validation.check_in_range("dest", 9, 4)


def test_jax_version_parse():
    assert jax_compat._parse("0.9.0") == (0, 9, 0)
    assert jax_compat._parse("0.10.1.dev2") >= (0, 10, 1)


def test_reduce_op_coercion():
    assert m4j.as_reduce_op("sum") is m4j.SUM
    assert m4j.as_reduce_op(m4j.MAX) is m4j.MAX
    with pytest.raises(TypeError):
        m4j.as_reduce_op(42)


def test_reduce_op_dtype_domains():
    with pytest.raises(TypeError):
        m4j.BAND.check_dtype(jnp.float32)
    m4j.BAND.check_dtype(jnp.uint8)
    m4j.LAND.check_dtype(jnp.bool_)
    with pytest.raises(TypeError):
        m4j.SUM.check_dtype(jnp.bool_)


def test_has_ici_support_runs():
    assert isinstance(m4j.has_ici_support(), bool)


def test_flush_runs():
    # the atexit barrier must be callable at any time
    from mpi4jax_tpu import _flush

    _flush()


def test_comm_context_stack():
    comm = m4j.MeshComm("foo")
    assert m4j.current_comm() is None
    with comm:
        assert m4j.current_comm() is comm
        inner = m4j.MeshComm("bar")
        with inner:
            assert m4j.current_comm() is inner
        assert m4j.current_comm() is comm
    assert m4j.current_comm() is None


def test_mesh_comm_hashable():
    a, b = m4j.MeshComm("x"), m4j.MeshComm("x")
    assert a == b and hash(a) == hash(b)
    assert m4j.MeshComm(("x", "y")) != a


def test_explicit_token_ordering_is_in_jit_cache_key():
    # the ordering mode is a jax config state in the jit cache key: a
    # function traced in one mode must retrace (not silently reuse the
    # cached program) when called in the other
    from mpi4jax_tpu.ops import _world_impl

    traces = []

    @jax.jit
    def f(x):
        traces.append(_world_impl._ordered_now())
        return x + 1

    f(jnp.zeros(2))
    with m4j.explicit_token_ordering():
        assert not _world_impl._ordered_now()
        f(jnp.zeros(2))
    assert _world_impl._ordered_now()
    f(jnp.zeros(2))  # cached ordered trace — no third retrace
    assert traces == [True, False]


def test_explicit_token_ordering_effect_selection():
    # primitives bind the unordered effect inside the context, ordered
    # outside — checked at the jaxpr level, no transport needed
    from mpi4jax_tpu.ops import _world_impl
    from mpi4jax_tpu.runtime.transport import WorldComm
    from mpi4jax_tpu.utils.effects import (
        comm_effect, unordered_comm_effect,
    )

    comm = WorldComm(rank=0, size=2, coord="127.0.0.1:45999")

    def prog(x):
        return _world_impl.allreduce(x, m4j.SUM, comm)

    ordered_jaxpr = jax.make_jaxpr(prog)(jnp.zeros(2))
    assert comm_effect in ordered_jaxpr.effects
    with m4j.explicit_token_ordering():
        unordered_jaxpr = jax.make_jaxpr(prog)(jnp.zeros(2))
    assert unordered_comm_effect in unordered_jaxpr.effects
    assert comm_effect not in unordered_jaxpr.effects


def test_native_build_failure_raises(tmp_path, monkeypatch):
    # no transport-only fallback and no stale library: a build that
    # fails raises with the compiler's output and leaves nothing behind
    from mpi4jax_tpu.runtime import bridge

    so, stamp = tmp_path / "lib.so", tmp_path / "lib.so.sha256"
    monkeypatch.setattr(bridge, "_SO_PATH", str(so))
    monkeypatch.setattr(bridge, "_STAMP_PATH", str(stamp))
    monkeypatch.setenv("CXX", "false")
    with pytest.raises(RuntimeError, match="building the native transport"):
        bridge._build(bridge._source_hash())
    assert list(tmp_path.iterdir()) == []


def test_native_staleness_keys_on_source_hash(tmp_path, monkeypatch):
    # a library is current only when the stamp beside it names the hash
    # of these sources: a .so copied in from another build is rebuilt
    from mpi4jax_tpu.runtime import bridge

    so, stamp = tmp_path / "lib.so", tmp_path / "lib.so.sha256"
    monkeypatch.setattr(bridge, "_SO_PATH", str(so))
    monkeypatch.setattr(bridge, "_STAMP_PATH", str(stamp))
    digest = bridge._source_hash()
    assert bridge._stale(digest)            # nothing built
    so.write_bytes(b"")
    assert bridge._stale(digest)            # a library with no stamp
    stamp.write_text("0" * 64 + "\n")
    assert bridge._stale(digest)            # built from other sources
    stamp.write_text(digest + "\n")
    assert not bridge._stale(digest)


@pytest.mark.parametrize("preset", [True, False])
def test_compile_cache_dir(tmp_path, monkeypatch, preset):
    # JAX_COMPILATION_CACHE_DIR when set, and nothing else; else one
    # fixed path in the checkout.  Either way it is exported, so that
    # launcher ranks share it, and handed to an imported jax
    import os

    from mpi4jax_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    if preset:
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(compile_cache._REPO_ROOT, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.use_compile_cache() == want
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert list(tmp_path.iterdir()) == []
