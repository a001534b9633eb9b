"""The paged decode-attention kernel (interpret mode on the CPU) against the
``gathered`` composite it replaces on the TPU: scrambled page tables,
prefix pages shared by two slots, the trash page bound, lengths at and
around page edges, group sizes 1 and 8, head sizes 64 and 128 — and a
slot's result is bitwise the same whichever physical pages hold its
keys."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops, ref

PAGE_LEN, PPS, HKV = 16, 4, 2
FULL = PAGE_LEN * PPS
#: one slot per case: a single position, one whole page, one past it, full
LENGTHS = (1, PAGE_LEN, PAGE_LEN + 1, FULL)


def _pools(key, n_pages, hd, dtype=jnp.float32):
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (n_pages, PAGE_LEN, HKV, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (n_pages, PAGE_LEN, HKV, hd)).astype(dtype)
    return k, v


def _table(rng, slots):
    """Scrambled private pages, slot 1 sharing slot 0's first two pages
    (a shared prefix), and the trash page 0 bound inside slot 3's run."""
    phys = rng.permutation(np.arange(1, slots * PPS + 1)).reshape(slots, PPS)
    phys[1, :2] = phys[0, :2]
    phys[3, 1] = 0
    return jnp.asarray(phys, jnp.int32)


def _kernel(q, k, v, ptab, lens, pages_per_block=2):
    return ops.paged_attention(q, k, v, ptab, lens,
                               pages_per_block=pages_per_block,
                               interpret=True)


@pytest.mark.parametrize("grp,hd", [(1, 64), (8, 64), (1, 128), (8, 128)])
def test_kernel_matches_gathered(grp, hd):
    key = jax.random.PRNGKey(grp * 1000 + hd)
    slots = len(LENGTHS)
    q = jax.random.normal(key, (slots, 1, HKV * grp, hd))
    k, v = _pools(key, slots * PPS + 1, hd)
    ptab = _table(np.random.default_rng(hd + grp), slots)
    lens = jnp.asarray(LENGTHS, jnp.int32)
    want = ref.paged_attention_gathered(q, k, v, ptab, lens)
    got = _kernel(q, k, v, ptab, lens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pages_per_block", [1, 3, PPS])
def test_kernel_block_size_leaves_result(pages_per_block):
    """Blocks of 1, 3 (the last one ragged) and all pages agree."""
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (4, 1, 8, 128))
    k, v = _pools(key, 4 * PPS + 1, 128)
    ptab = _table(np.random.default_rng(5), 4)
    lens = jnp.asarray([FULL, 40, 3, PAGE_LEN * 3 + 1], jnp.int32)
    want = ref.paged_attention_gathered(q, k, v, ptab, lens)
    got = _kernel(q, k, v, ptab, lens, pages_per_block)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_kernel_bf16_matches_gathered():
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (4, 1, 16, 128)).astype(jnp.bfloat16)
    k, v = _pools(key, 4 * PPS + 1, 128, jnp.bfloat16)
    ptab = _table(np.random.default_rng(9), 4)
    lens = jnp.asarray(LENGTHS, jnp.int32)
    want = ref.paged_attention_gathered(q, k, v, ptab, lens)
    got = _kernel(q, k, v, ptab, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_rebinding_identical_bytes_is_bitwise():
    """Moving every page to another physical index, and giving slot 1
    private copies of the prefix pages it shared, leaves each slot's
    output bitwise unchanged."""
    key = jax.random.PRNGKey(3)
    slots, hd = 4, 128
    q = jax.random.normal(key, (slots, 1, 16, hd))
    k, v = _pools(key, 2 * slots * PPS + 1, hd)
    ptab = np.asarray(_table(np.random.default_rng(3), slots))
    lens = jnp.asarray([FULL, 40, PAGE_LEN + 1, FULL], jnp.int32)
    base = np.asarray(_kernel(q, k, v, jnp.asarray(ptab), lens))

    # a permutation of physical pages: page p moves to perm[p]
    perm = np.random.default_rng(4).permutation(k.shape[0])
    inv = np.argsort(perm)
    moved_tab = perm[ptab]
    got = _kernel(q, k[inv], v[inv], jnp.asarray(moved_tab), lens)
    np.testing.assert_array_equal(np.asarray(got), base)

    # slot 1's shared prefix pages copied into free private pages
    free = np.arange(slots * PPS + 1, slots * PPS + 3)
    k2 = k.at[free].set(k[ptab[1, :2]])
    v2 = v.at[free].set(v[ptab[1, :2]])
    private = ptab.copy()
    private[1, :2] = free
    got = _kernel(q, k2, v2, jnp.asarray(private), lens)
    np.testing.assert_array_equal(np.asarray(got), base)


def test_lengths_clamp_to_the_slot():
    """A length past the slot's pages reads the whole slot, as the
    gathered view's mask does."""
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (4, 1, 8, 128))
    k, v = _pools(key, 4 * PPS + 1, 128)
    ptab = _table(np.random.default_rng(2), 4)
    over = jnp.asarray([FULL + 9, 1, 2, 3], jnp.int32)
    np.testing.assert_allclose(
        _kernel(q, k, v, ptab, over),
        ref.paged_attention_gathered(q, k, v, ptab, over),
        rtol=2e-4, atol=2e-4)


def test_block_size_and_shape_rules():
    # the published geometry: 64-position pages of 2 KV heads x 128, bf16
    assert ops.page_bytes(64, 2, 128, 2) == 32 * 1024
    assert ops.pick_pages_per_block(64, 2, 128, 2, 64) == 8
    assert ops.pick_pages_per_block(64, 2, 128, 2, 2) == 2
    assert ops.kernel_unsupported(16, 1, 16, 2, 128, 64, 64, 2) == ""
    assert "one query row" in ops.kernel_unsupported(16, 2, 16, 2, 128, 64,
                                                     64, 2)
    assert "lane" in ops.kernel_unsupported(16, 1, 16, 2, 64, 64, 64, 2)
    assert "tiling" in ops.kernel_unsupported(16, 1, 16, 1, 128, 8, 64, 2)
    assert "scalar memory" in ops.kernel_unsupported(1024, 1, 16, 2, 128,
                                                     64, 64, 2)
