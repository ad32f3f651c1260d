"""Pallas L0 kernels: bit-parity with the jnp reference paths
(ref: SURVEY §1 L0 — the cudf-native-kernel layer, re-done for the
VPU).  On CPU the kernels run in interpret mode; the real TPU path
compiles the same kernel."""

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.exprs.hashing import hash_string_bytes
from spark_rapids_tpu.ops.pallas_kernels import (
    _BLOCK_N,
    pallas_hash_string,
)


def _string_matrix(n, width, seed, max_len=None):
    rng = np.random.default_rng(seed)
    chars = rng.integers(0, 256, (n, width), dtype=np.uint8)
    lengths = rng.integers(0, (max_len or width) + 1, n,
                           dtype=np.int32)
    # zero out bytes past each row's length (layout invariant)
    mask = np.arange(width)[None, :] < lengths[:, None]
    chars = np.where(mask, chars, 0).astype(np.uint8)
    return jnp.asarray(chars), jnp.asarray(lengths)


@pytest.mark.parametrize("width", [4, 8, 12, 20])
@pytest.mark.slow
def test_pallas_string_hash_parity(width):
    n = _BLOCK_N * 2
    chars, lengths = _string_matrix(n, width, seed=width)
    seeds = jnp.full((n,), 42, jnp.uint32)
    ref = hash_string_bytes(chars, lengths, jnp.uint32(42))
    got = pallas_hash_string(chars, lengths, seeds, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_pallas_string_hash_chained_seeds():
    # per-row seeds (the multi-column chain): must thread through
    n = _BLOCK_N
    chars, lengths = _string_matrix(n, 8, seed=99)
    seeds = jnp.arange(n, dtype=jnp.uint32)
    got = pallas_hash_string(chars, lengths, seeds, interpret=True)
    ref = hash_string_bytes(chars, lengths, seeds)  # jnp path on CPU
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_pallas_gate_is_a_rule_not_a_rescue(monkeypatch):
    import spark_rapids_tpu.ops.pallas_kernels as PK

    # a non-TPU backend takes the jnp path: that is the routing rule
    assert PK.pallas_available() is False  # tests pin the CPU backend
    n = _BLOCK_N
    chars, lengths = _string_matrix(n, 8, seed=5)
    seeds = jnp.full((n,), 42, jnp.uint32)
    assert PK.maybe_pallas_hash_string(chars, lengths, seeds) is None

    # a backend that cannot be asked is an error the caller sees, not
    # a quiet "use jnp"
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(PK.jax, "default_backend", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        PK.pallas_available()


def test_empty_and_full_width_strings():
    n = _BLOCK_N
    width = 8
    chars = jnp.zeros((n, width), jnp.uint8)
    lengths = jnp.concatenate(
        [jnp.zeros(n // 2, jnp.int32),
         jnp.full(n // 2, width, jnp.int32)])
    seeds = jnp.full((n,), 42, jnp.uint32)
    ref = hash_string_bytes(chars, lengths, jnp.uint32(42))
    got = pallas_hash_string(chars, lengths, seeds, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_subblock_batches_coalesce_into_one_block(monkeypatch):
    """Tail batches below _BLOCK_N pad into one kernel block instead
    of falling to the width-specialized jnp path (ISSUE 11: tiny tail
    batches must not each mint their own lowering) — results match
    the reference bit-for-bit and the padding rows are sliced away."""
    import spark_rapids_tpu.ops.pallas_kernels as PK

    monkeypatch.setattr(PK, "pallas_available", lambda: True)
    calls = []

    def interp(chars, lengths, seeds):
        calls.append(chars.shape)
        return pallas_hash_string(chars, lengths, seeds,
                                  interpret=True)

    monkeypatch.setattr(PK, "pallas_hash_string", interp)
    for n in (8, 256, _BLOCK_N // 2):
        chars, lengths = _string_matrix(n, 8, seed=n)
        seeds = jnp.full((n,), 42, jnp.uint32)
        got = PK.maybe_pallas_hash_string(chars, lengths, seeds)
        assert got is not None and got.shape == (n,)
        # the kernel saw exactly one full block
        assert calls[-1] == (_BLOCK_N, 8)
        ref = hash_string_bytes(chars, lengths, jnp.uint32(42))
        assert np.array_equal(np.asarray(got), np.asarray(ref))
    # full-block shapes pass through unpadded; over-wide refuses
    chars, lengths = _string_matrix(_BLOCK_N, 8, seed=1)
    seeds = jnp.full((_BLOCK_N,), 42, jnp.uint32)
    assert PK.maybe_pallas_hash_string(chars, lengths, seeds) \
        is not None
    assert calls[-1] == (_BLOCK_N, 8)
    wide = jnp.zeros((_BLOCK_N, 256), jnp.uint8)
    assert PK.maybe_pallas_hash_string(
        wide, jnp.zeros(_BLOCK_N, jnp.int32), seeds) is None


def test_wide_blocks_pad_off_multiple_shapes(monkeypatch):
    """Over-block off-multiple shapes — the 3*pow2/2 occupancy bucket
    (1536 = capacity.policy=pow2x3) and coalesced multi-batch blocks —
    pad up to the next _BLOCK_N multiple and run the same grid-blocked
    kernel instead of falling to the jnp path (ISSUE 17 wide blocks).
    The grid covers the live region; pad rows hash as empty strings
    and are sliced away bit-exactly."""
    import spark_rapids_tpu.ops.pallas_kernels as PK

    monkeypatch.setattr(PK, "pallas_available", lambda: True)
    calls = []

    def interp(chars, lengths, seeds):
        calls.append(chars.shape)
        return pallas_hash_string(chars, lengths, seeds,
                                  interpret=True)

    monkeypatch.setattr(PK, "pallas_hash_string", interp)
    for n in (_BLOCK_N * 3 // 2, _BLOCK_N * 2 + 8, _BLOCK_N * 3):
        chars, lengths = _string_matrix(n, 8, seed=n)
        seeds = jnp.full((n,), 42, jnp.uint32)
        got = PK.maybe_pallas_hash_string(chars, lengths, seeds)
        assert got is not None and got.shape == (n,)
        blocks = -(-n // _BLOCK_N)
        assert calls[-1] == (blocks * _BLOCK_N, 8)
        ref = hash_string_bytes(chars, lengths, jnp.uint32(42))
        assert np.array_equal(np.asarray(got), np.asarray(ref))
