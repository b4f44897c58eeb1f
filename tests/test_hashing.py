"""Hash bit-exactness tests (SURVEY.md §4.2 items 1-2).

Three implementations — jnp (device), NumPy (oracle), C++ (native) — must
agree with each other and with published MurmurHash3_x86_32 / FNV-1a test
vectors on every input hypothesis can dream up.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need it; skip cleanly without
from hypothesis import given, settings
from hypothesis import strategies as st

from tpubloom import native
from tpubloom.cpu_ref import fnv1a_32_np, murmur3_32_np, positions_np
from tpubloom.ops import hashing
from tpubloom.utils.packing import pack_keys

# Published MurmurHash3_x86_32 test vectors (widely circulated reference
# values for Appleby's canonical implementation).
MURMUR3_VECTORS = [
    (b"", 0x00000000, 0x00000000),
    (b"", 0x00000001, 0x514E28B7),
    (b"", 0xFFFFFFFF, 0x81F16F39),
    (b"\x00\x00\x00\x00", 0x00000000, 0x2362F9DE),
    (b"a", 0x9747B28C, 0x7FA09EA6),
    (b"aa", 0x9747B28C, 0x5D211726),
    (b"aaa", 0x9747B28C, 0x283E0130),
    (b"aaaa", 0x9747B28C, 0x5A97808A),
    (b"ab", 0x9747B28C, 0x74875592),
    (b"abc", 0x9747B28C, 0xC84A62DD),
    (b"abcd", 0x9747B28C, 0xF0478627),
    (b"Hello, world!", 0x9747B28C, 0x24884CBA),
    (b"The quick brown fox jumps over the lazy dog", 0x9747B28C, 0x2FA826CD),
]

FNV1A_VECTORS = [
    (b"", 0x811C9DC5),
    (b"a", 0xE40C292C),
    (b"b", 0xE70C2DE5),
    (b"foobar", 0xBF9CF968),
]

KEY_LEN = 48  # fits every vector above


def _pack(keys):
    return pack_keys(keys, KEY_LEN)


@pytest.mark.parametrize("key,seed,want", MURMUR3_VECTORS)
def test_murmur3_published_vectors(key, seed, want):
    ks, ls = _pack([key])
    assert int(murmur3_32_np(ks, ls, seed)[0]) == want
    assert int(hashing.murmur3_32(jnp.asarray(ks), jnp.asarray(ls), seed)[0]) == want
    assert int(native.murmur3_batch(ks, ls, seed)[0]) == want


@pytest.mark.parametrize("key,want", FNV1A_VECTORS)
def test_fnv1a_published_vectors(key, want):
    ks, ls = _pack([key])
    assert int(fnv1a_32_np(ks, ls)[0]) == want
    assert int(hashing.fnv1a_32(jnp.asarray(ks), jnp.asarray(ls))[0]) == want
    assert int(native.fnv1a_batch(ks, ls)[0]) == want


@given(
    keys=st.lists(st.binary(min_size=0, max_size=KEY_LEN), min_size=1, max_size=64),
    seed=st.integers(0, 2**32 - 1),
)
@settings(max_examples=200, deadline=None)
def test_murmur3_three_way_parity(keys, seed):
    ks, ls = _pack(keys)
    ref = murmur3_32_np(ks, ls, seed)
    dev = np.asarray(hashing.murmur3_32(jnp.asarray(ks), jnp.asarray(ls), seed))
    nat = native.murmur3_batch(ks, ls, seed)
    np.testing.assert_array_equal(dev, ref)
    np.testing.assert_array_equal(nat, ref)


@given(keys=st.lists(st.binary(min_size=0, max_size=KEY_LEN), min_size=1, max_size=64))
@settings(max_examples=100, deadline=None)
def test_fnv1a_three_way_parity(keys):
    ks, ls = _pack(keys)
    ref = fnv1a_32_np(ks, ls)
    dev = np.asarray(hashing.fnv1a_32(jnp.asarray(ks), jnp.asarray(ls)))
    nat = native.fnv1a_batch(ks, ls)
    np.testing.assert_array_equal(dev, ref)
    np.testing.assert_array_equal(nat, ref)


def test_padding_never_changes_hash():
    # Same key packed into buffers of different static length must hash alike.
    key = b"tpubloom"
    for L in (8, 12, 16, 32, 48):
        ks, ls = pack_keys([key], L)
        assert int(murmur3_32_np(ks, ls, 7)[0]) == int(
            murmur3_32_np(*pack_keys([key], 64), 7)[0]
        )
        assert int(hashing.murmur3_32(jnp.asarray(ks), jnp.asarray(ls), 7)[0]) == int(
            murmur3_32_np(ks, ls, 7)[0]
        )


@pytest.mark.parametrize(
    "m", [10_000_000, 1 << 20, 1 << 32, 1 << 34, 1 << 36]
)
def test_positions_three_way_parity(m):
    """Exercises both position paths: 32-bit mod (m=10M) and 64-bit pow2
    (incl. m > 2^32, the sharded config-5 scale)."""
    rng = np.random.default_rng(42)
    keys = [rng.bytes(rng.integers(1, KEY_LEN + 1)) for _ in range(256)]
    ks, ls = _pack(keys)
    k, seed = 7, 0x9747B28C
    ref = positions_np(ks, ls, m=m, k=k, seed=seed)
    nat = native.positions_batch(ks, ls, m=m, k=k, seed=seed)
    np.testing.assert_array_equal(nat, ref)
    ph, pl = hashing.positions(jnp.asarray(ks), jnp.asarray(ls), m=m, k=k, seed=seed)
    dev = np.asarray(ph).astype(np.uint64) << np.uint64(32) | np.asarray(pl).astype(
        np.uint64
    )
    np.testing.assert_array_equal(dev, ref)
    assert ref.max() < m


def test_positions_distribution_sanity():
    # Positions should spread over the whole range, all k slots distinct for
    # most keys (odd 64-bit stride).
    m, k = 1 << 30, 10
    rng = np.random.default_rng(0)
    keys = [rng.bytes(16) for _ in range(1000)]
    ks, ls = _pack(keys)
    pos = positions_np(ks, ls, m=m, k=k, seed=1)
    # coarse uniformity: mean near m/2, both halves populated
    assert 0.45 < pos.mean() / m < 0.55
    distinct = np.array([len(set(row)) for row in pos])
    assert (distinct == k).mean() > 0.99


def test_word_bit_split():
    ph = jnp.asarray([[0, 1]], jnp.uint32)  # pos_hi=1 => pos >= 2^32
    pl = jnp.asarray([[37, 37]], jnp.uint32)
    word, bit = hashing.split_word_bit(ph, pl)
    assert int(word[0, 0]) == 37 >> 5 and int(bit[0, 0]) == 37 & 31
    assert int(word[0, 1]) == (1 << 27) | (37 >> 5)


def test_native_library_is_keyed_on_source_and_flags(monkeypatch, tmp_path):
    """The native library's name carries a hash of the committed source
    and the compile flags: an edited source or changed flags name a new
    library (rebuilt), never a stale one found by mtime."""
    from tpubloom import native

    path = native._lib_path()
    assert os.path.basename(path).startswith("libbloomhash-")
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ("-DX=1",))
    assert native._lib_path() != path
    monkeypatch.undo()
    src = tmp_path / "bloomhash.cpp"
    src.write_bytes(open(native._SRC, "rb").read() + b"\n// edit\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    assert os.path.basename(native._lib_path()) != os.path.basename(path)
