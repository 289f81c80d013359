"""Block-size autotuner: tune -> persist -> reload, and kernel integration
via block=None (opt-in: defaults stay untouched when disabled) -- for the
GEMMs and the SWAR kernels (simd_add / mul4 / muladd2)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import (autotune, common, mul4, muladd2, quant_matmul,
                           ref, simd_add)


@pytest.fixture
def tuner_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    yield
    autotune._cache = None   # don't leak tmp cache into other tests


def test_disabled_resolve_is_default(tuner_cache, monkeypatch):
    monkeypatch.setattr(autotune, "_enabled", False)
    assert autotune.resolve("quant_matmul", 8, 128, 256) == \
        autotune.DEFAULT_BLOCK


def test_tune_persists_and_reloads(tuner_cache):
    fast = ((128, 128, 256), (256, 256, 512))
    blk = autotune.tune("quant_matmul", 8, 128, 256, candidates=fast,
                        iters=1)
    assert blk in fast
    assert autotune.lookup("quant_matmul", 8, 128, 256) == blk
    autotune._cache = None                       # force re-read from disk
    assert autotune.lookup("quant_matmul", 8, 128, 256) == blk
    # resolve() now serves the persisted winner even with tuning disabled
    assert autotune.resolve("quant_matmul", 8, 128, 256) == blk


def test_block_none_uses_tuned_block_and_stays_correct(tuner_cache, rng):
    autotune.tune("quant_matmul", 8, 128, 256,
                  candidates=((128, 128, 256),), iters=1)
    x = jnp.asarray(rng.integers(-128, 128, (8, 128)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (128, 256)), jnp.int8)
    got = quant_matmul.quant_matmul_acc(x, w)    # block=None -> tuned
    want = np.asarray(x, np.int64) @ np.asarray(w, np.int64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


# ---------------------------------------------------------------------------
# SWAR kernel coverage (2-D blocks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,dims", [
    ("simd_add", (8, 128)),
    ("mul4", (32, 128)),
    ("mul4_split", (32, 128)),
    ("muladd2", (2, 32, 128)),
])
def test_swar_tune_persists_and_reloads(tuner_cache, kind, dims):
    blk = autotune.tune(kind, *dims, candidates=((64, 128),), iters=1)
    assert blk == (64, 128)
    assert autotune.lookup(kind, *dims) == blk
    autotune._cache = None                       # force re-read from disk
    assert autotune.resolve(kind, *dims) == blk


def test_swar_disabled_resolve_is_2d_default(tuner_cache, monkeypatch):
    monkeypatch.setattr(autotune, "_enabled", False)
    for kind in ("simd_add", "mul4", "muladd2"):
        assert autotune.resolve(kind, 8, 128) == autotune.DEFAULT_BLOCK_2D


def test_cache_keys_separate_lowering_and_mode(tuner_cache):
    """Regression (v1 -> v2 keys): entries tuned for one lowering or
    execution mode must never shadow another -- interpret-mode CPU tuning
    used to collide with real TPU timings for the same shapes."""
    autotune.tune("quant_matmul", 8, 128, 256, candidates=((128, 128, 256),),
                  iters=1, lowering="tpu-pallas", interpret=True)
    # same kind+shape, different lowering / mode: all misses
    assert autotune.lookup("quant_matmul", 8, 128, 256,
                           lowering="gpu-pallas", interpret=True) is None
    assert autotune.lookup("quant_matmul", 8, 128, 256,
                           lowering="tpu-pallas", interpret=False) is None
    assert autotune.lookup("quant_matmul", 8, 128, 256,
                           lowering="tpu-pallas", interpret=True) == \
        (128, 128, 256)
    # the gpu lowering tunes into its own slot without clobbering
    autotune.tune("quant_matmul", 8, 128, 256, candidates=((64, 64, 64),),
                  iters=1, lowering="gpu-pallas", interpret=True)
    assert autotune.lookup("quant_matmul", 8, 128, 256,
                           lowering="gpu-pallas", interpret=True) == \
        (64, 64, 64)
    assert autotune.lookup("quant_matmul", 8, 128, 256,
                           lowering="tpu-pallas", interpret=True) == \
        (128, 128, 256)
    # every persisted key carries the v2 version tag
    assert all(k.startswith(f"v{autotune.CACHE_VERSION}:")
               for k in autotune._load())
    # non-Pallas lowerings have no tunable kernels: timing one would
    # persist a mislabeled entry, so tune() refuses outright
    with pytest.raises(ValueError, match="tunable"):
        autotune.tune("quant_matmul", 8, 128, 256, lowering="cpu-vector")


def test_simd_add_block_none_stays_correct(tuner_cache, rng):
    autotune.tune("simd_add", 8, 128, candidates=((64, 128),), iters=1)
    x = jnp.asarray(rng.integers(0, 1 << 32, (8, 128), dtype=np.uint32))
    y = jnp.asarray(rng.integers(0, 1 << 32, (8, 128), dtype=np.uint32))
    got = simd_add.simd_add_packed(x, y)         # block=None -> tuned
    lanes = zip(common.unpack_lanes(x, 8), common.unpack_lanes(y, 8))
    want = common.pack_lanes([a + b for a, b in lanes], 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_muladd2_block_none_stays_correct(tuner_cache, rng):
    autotune.tune("muladd2", 2, 32, 128, candidates=((64, 128),), iters=1)
    a = jnp.asarray(rng.integers(-8, 8, (2, 32, 128)), jnp.int8)
    b = jnp.asarray(rng.integers(-8, 8, (2, 32, 128)), jnp.int8)
    c = jnp.asarray(rng.integers(-128, 128, (2, 32, 128)), jnp.int8)
    pa, pb = muladd2.muladd2(a, b, c)            # block=None -> tuned
    ra, rb = ref.muladd2_ref(list(a), list(b), list(c))
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(ra))
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(rb))


def test_mul4_block_none_stays_correct(tuner_cache, rng):
    # full32 and split tune as SEPARATE kinds (different cost profiles)
    autotune.tune("mul4", 32, 128, candidates=((64, 128),), iters=1)
    autotune.tune("mul4_split", 32, 128, candidates=((128, 256),), iters=1)
    assert autotune.lookup("mul4", 32, 128) == (64, 128)
    assert autotune.lookup("mul4_split", 32, 128) == (128, 256)
    a = jnp.asarray(rng.integers(-8, 8, (4, 32, 128)), jnp.int8)
    b = jnp.asarray(rng.integers(-8, 8, (32, 128)), jnp.int8)
    want = ref.mul4_ref(list(a), b)
    for got in (mul4.mul4_full32(a, b),          # block=None -> tuned
                mul4.mul4_split(a, b)):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_tune_raises_when_every_native_block_fails(tuner_cache):
    # a Mosaic kernel cannot run natively on the CPU backend, so every
    # candidate fails there: tuning raises with the first error instead of
    # quietly handing back the default block, and records nothing
    with pytest.raises(RuntimeError, match="every quant_matmul block"):
        autotune.tune("quant_matmul", 8, 128, 256,
                      candidates=((128, 128, 256),), iters=1,
                      lowering="tpu-pallas", interpret=False)
    assert autotune.lookup("quant_matmul", 8, 128, 256,
                           lowering="tpu-pallas", interpret=False) is None
