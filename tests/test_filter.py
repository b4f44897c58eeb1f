"""BloomFilter end-to-end tests vs the CPU oracle (SURVEY.md §4.2;
BASELINE config 1: 1M random 16-byte keys, m=10M bits, k=7 — scaled down
for CI speed, the full config runs in benchmarks/)."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need it; skip cleanly without
from hypothesis import given, settings
from hypothesis import strategies as st

from tpubloom import BloomFilter, CPUBloomFilter, FilterConfig
from tpubloom.params import theoretical_fpr


def _rand_keys(n, rng, nbytes=16):
    return [rng.bytes(nbytes) for _ in range(n)]


@pytest.fixture(scope="module")
def config1():
    # BASELINE config 1 shape: m=10M (non-pow2 -> 32-bit path), k=7.
    return FilterConfig(m=10_000_000, k=7, key_len=16)


def test_roundtrip_no_false_negatives(config1):
    rng = np.random.default_rng(0)
    keys = _rand_keys(5000, rng)
    f = BloomFilter(config1)
    f.insert_batch(keys)
    assert f.include_batch(keys).all(), "bloom filters never have false negatives"


def test_absent_keys_mostly_absent(config1):
    rng = np.random.default_rng(1)
    f = BloomFilter(config1)
    f.insert_batch(_rand_keys(5000, rng))
    absent = _rand_keys(5000, rng)
    fpr = f.include_batch(absent).mean()
    assert fpr < 0.01  # 5k keys in 10M bits: theoretical FPR ~ 0


def test_membership_parity_vs_oracle(config1):
    """Bit-for-bit: device filter and CPU oracle answer identically, and the
    underlying bit arrays are identical (SURVEY.md §4.2 item 6)."""
    rng = np.random.default_rng(2)
    keys = _rand_keys(2000, rng) + [b"", b"a", b"tpubloom" * 2]
    keys += keys[:17]  # duplicates in the same batch
    f = BloomFilter(config1)
    o = CPUBloomFilter(config1)
    f.insert_batch(keys)
    o.insert_batch(keys)
    np.testing.assert_array_equal(np.asarray(f.words), o.words)
    probe = keys + _rand_keys(2000, rng)
    np.testing.assert_array_equal(f.include_batch(probe), o.include_batch(probe))


@pytest.mark.parametrize("m", [1 << 20, 1 << 21])
def test_statistical_fpr(m):
    """Observed FPR tracks (1-e^{-kn/m})^k within slack (SURVEY.md §4.2.4)."""
    k, n = 7, 100_000
    f = BloomFilter(FilterConfig(m=m, k=k, key_len=16))
    rng = np.random.default_rng(4)
    f.insert_batch(_rand_keys(n, rng))
    probes = _rand_keys(50_000, rng)
    observed = float(f.include_batch(probes).mean())
    expected = theoretical_fpr(m, k, n)
    assert observed < expected * 1.5 + 1e-4
    if expected > 1e-3:
        assert observed > expected * 0.5


def test_pow2_path_parity():
    cfg = FilterConfig(m=1 << 22, k=5, key_len=16)
    rng = np.random.default_rng(5)
    keys = _rand_keys(3000, rng)
    f, o = BloomFilter(cfg), CPUBloomFilter(cfg)
    f.insert_batch(keys)
    o.insert_batch(keys)
    np.testing.assert_array_equal(np.asarray(f.words), o.words)
    probe = _rand_keys(3000, rng) + keys[:100]
    np.testing.assert_array_equal(f.include_batch(probe), o.include_batch(probe))


def test_scalar_api_and_clear(config1):
    f = BloomFilter(config1)
    f.insert(b"hello")
    f.insert("héllo-str")
    assert f.include(b"hello") and f.include("héllo-str")
    assert b"hello" in f
    assert not f.include(b"absent-key")
    f.clear()
    assert not f.include(b"hello")
    assert f.n_inserted == 0


def test_variable_length_and_empty_keys(config1):
    f, o = BloomFilter(config1), CPUBloomFilter(config1)
    keys = [b"", b"a", b"ab", b"abc", b"abcd", b"abcde", b"0123456789abcdef"]
    f.insert_batch(keys)
    o.insert_batch(keys)
    np.testing.assert_array_equal(np.asarray(f.words), o.words)
    assert f.include_batch(keys).all()


@given(
    keys=st.lists(st.binary(min_size=0, max_size=16), min_size=1, max_size=100),
    probes=st.lists(st.binary(min_size=0, max_size=16), min_size=1, max_size=100),
)
@settings(max_examples=30, deadline=None)
def test_hypothesis_parity(keys, probes):
    cfg = FilterConfig(m=1 << 16, k=4, key_len=16)
    f, o = BloomFilter(cfg), CPUBloomFilter(cfg)
    f.insert_batch(keys)
    o.insert_batch(keys)
    np.testing.assert_array_equal(f.include_batch(probes), o.include_batch(probes))


def test_redis_bitmap_interop(config1):
    """A :jax-built filter exported as a Redis bitmap answers identically
    when re-imported by the CPU oracle, and vice versa."""
    rng = np.random.default_rng(6)
    keys = _rand_keys(1000, rng)
    f = BloomFilter(config1)
    f.insert_batch(keys)
    o = CPUBloomFilter.from_redis_bitmap(config1, f.to_redis_bitmap())
    assert o.include_batch(keys).all()
    np.testing.assert_array_equal(o.words, np.asarray(f.words))
    f2 = BloomFilter.from_redis_bitmap(config1, o.to_redis_bitmap())
    assert f2.include_batch(keys).all()


def test_fill_ratio_and_stats(config1):
    f = BloomFilter(config1)
    rng = np.random.default_rng(7)
    f.insert_batch(_rand_keys(10_000, rng))
    s = f.stats()
    expect_fill = 1 - np.exp(-7 * 10_000 / 10_000_000)
    assert abs(s["fill_ratio"] - expect_fill) / expect_fill < 0.05
    assert s["n_inserted"] == 10_000


def test_big_m_virtual_34bit():
    """m=2^34 (config 3 scale) positions exceed u32 — exercise the 64-bit
    path end to end on CPU with a sparse probe set (2 GiB array is fine on
    host RAM)."""
    cfg = FilterConfig(m=1 << 34, k=3, key_len=16)
    f = BloomFilter(cfg)
    keys = [b"key-%d" % i for i in range(100)]
    f.insert_batch(keys)
    assert f.include_batch(keys).all()
    assert not f.include_batch([b"absent-%d" % i for i in range(100)]).any()


@pytest.mark.parametrize("m", [1 << 20, 1 << 32, 1 << 36])
def test_popcount_fill_takes_any_m(m):
    """Stats and /metrics divide the set bits by m: an m of 2^31 or more
    must not overflow JAX's int32 argument (it failed every /metrics
    scrape at the north-star m=2^32)."""
    import jax.numpy as jnp

    from tpubloom.ops import bitops

    bits = jnp.full((64, 128), 0xFFFFFFFF, jnp.uint32)
    assert float(bitops.popcount_fill(bits, m)) == 64 * 128 * 32 / m
