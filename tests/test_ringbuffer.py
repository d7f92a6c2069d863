"""RingBuffer edge cases: backpressure, wraparound FIFO, empty dequeue."""
import numpy as np
import jax.numpy as jnp

from repro.data import create, dequeue, enqueue, size


def _items(vals):
    return jnp.asarray(np.asarray(vals, np.float32).reshape(-1, 1))


def test_enqueue_past_capacity_rejects():
    rb = create(4, (1,))
    rb, n = enqueue(rb, _items([1, 2, 3]))
    assert int(n) == 3
    # only one slot free: exactly one of the next batch is accepted
    rb, n = enqueue(rb, _items([4, 5, 6]))
    assert int(n) == 1
    assert int(size(rb)) == 4
    # completely full: everything rejected, nothing overwritten
    rb, n = enqueue(rb, _items([7, 8]))
    assert int(n) == 0
    rb, out, valid = dequeue(rb, 4)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), [1, 2, 3, 4])
    assert bool(valid.all())


def test_backpressure_accounting_over_many_batches(rng):
    """Sum of accepted counts always equals what dequeue can recover."""
    rb = create(8, (1,))
    accepted = consumed = 0
    for i in range(20):
        batch = _items(rng.standard_normal(5))
        rb, n = enqueue(rb, batch)
        accepted += int(n)
        assert 0 <= int(n) <= 5
        rb, out, valid = dequeue(rb, 3)
        consumed += int(valid.sum())
    assert int(size(rb)) == accepted - consumed
    assert accepted <= 20 * 5


def test_fifo_order_across_wraparound():
    rb = create(4, (1,))
    expect = []
    nxt = 0.0
    # drive many full/drain cycles so head/tail wrap the capacity often
    for _ in range(7):
        batch = [nxt, nxt + 1, nxt + 2]
        nxt += 3
        rb, n = enqueue(rb, _items(batch))
        expect += batch[: int(n)]
        rb, out, valid = dequeue(rb, 2)
        got = np.asarray(out[:, 0])[np.asarray(valid)]
        np.testing.assert_array_equal(got, expect[: len(got)])
        expect = expect[len(got):]


def test_dequeue_empty_returns_all_invalid_mask():
    rb = create(4, (2,))
    rb, out, valid = dequeue(rb, 3)
    assert out.shape == (3, 2)
    assert not bool(valid.any())
    assert int(size(rb)) == 0
    # and the buffer still works afterwards
    rb, n = enqueue(rb, jnp.ones((2, 2)))
    assert int(n) == 2
    rb, out, valid = dequeue(rb, 3)
    np.testing.assert_array_equal(np.asarray(valid), [True, True, False])


def test_enqueue_batch_larger_than_capacity():
    """Offering more than the whole ring in one call must accept
    exactly the free space and corrupt nothing (wrapped duplicate
    indices used to let rejected rows clobber accepted ones)."""
    rb = create(4, (1,))
    rb, n = enqueue(rb, _items([0, 1, 2, 3, 4, 5]))
    assert int(n) == 4
    rb, out, valid = dequeue(rb, 4)
    assert bool(valid.all())
    np.testing.assert_array_equal(np.asarray(out[:, 0]), [0, 1, 2, 3])


def test_dequeue_more_than_available():
    rb = create(8, (1,))
    rb, _ = enqueue(rb, _items([1, 2]))
    rb, out, valid = dequeue(rb, 5)
    np.testing.assert_array_equal(np.asarray(valid),
                                  [True, True, False, False, False])
    np.testing.assert_array_equal(np.asarray(out[:2, 0]), [1, 2])
    assert int(size(rb)) == 0


def test_int32_rows_carry_exact_ids_past_2_24():
    """An int32 ring moves integer words exactly: ids that float32 would
    round (2**24 + 1) come back as they went in, across wraparound."""
    rb = create(4, (2,), jnp.int32)
    ids = np.array([[2 ** 24 + 1, -7], [2 ** 31 - 1, 2 ** 24 + 3],
                    [5, -2 ** 31], [2 ** 30 + 1, 0]], np.int32)
    got = []
    for lo in (0, 2, 1, 3):
        rb, n = enqueue(rb, jnp.asarray(ids[lo:lo + 2]))
        rb, out, valid = dequeue(rb, 2)
        assert int(n) == valid.sum() == len(ids[lo:lo + 2])
        assert out.dtype == jnp.int32
        got.append(np.asarray(out)[np.asarray(valid)])
    np.testing.assert_array_equal(np.concatenate(got), np.concatenate(
        [ids[0:2], ids[2:4], ids[1:3], ids[3:4]]))
