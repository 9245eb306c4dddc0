"""Bounded-buffer transmitter: chunked == single-shot; masking correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transmitter


@pytest.mark.parametrize("buffer_rows", [1, 3, 7, 64])
def test_chunked_equals_single_shot(buffer_rows):
    rng = np.random.default_rng(0)
    src = {"w": jnp.asarray(rng.normal(size=(20, 4)).astype(np.float32)),
           "a": jnp.asarray(rng.normal(size=(20,)).astype(np.float32))}
    dst = {"w": jnp.zeros((10, 4)), "a": jnp.zeros((10,))}
    src_idx = jnp.asarray([3, 5, -1, 7, 0, 19, 2, -1], jnp.int32)
    dst_idx = jnp.asarray([0, 1, 2, 3, 4, 5, 6, 7], jnp.int32)
    active = src_idx >= 0
    out = transmitter.move_rows(src, dst, src_idx, dst_idx, active, buffer_rows=buffer_rows)
    ref_w = np.zeros((10, 4), np.float32)
    ref_a = np.zeros((10,), np.float32)
    for s, d in zip(np.asarray(src_idx), np.asarray(dst_idx)):
        if s >= 0:
            ref_w[d] = np.asarray(src["w"])[s]
            ref_a[d] = np.asarray(src["a"])[s]
    np.testing.assert_allclose(np.asarray(out["w"]), ref_w)
    np.testing.assert_allclose(np.asarray(out["a"]), ref_a)


def test_inactive_lanes_do_not_touch_dst():
    src = {"w": jnp.ones((4, 2))}
    dst = {"w": jnp.full((4, 2), 7.0)}
    out = transmitter.move_rows(
        src, dst,
        jnp.asarray([0, 1], jnp.int32), jnp.asarray([0, 1], jnp.int32),
        jnp.asarray([False, False]),
        buffer_rows=2,
    )
    np.testing.assert_array_equal(np.asarray(out["w"]), np.full((4, 2), 7.0))


def test_num_rounds():
    assert transmitter.num_rounds(10, 3) == 4
    assert transmitter.num_rounds(9, 3) == 3
    assert transmitter.num_rounds(1, 64) == 1


# ---------------------------------------------------------------------------
# live_lanes: only the rounds that reach the front-compacted active lanes run
# ---------------------------------------------------------------------------

_K, _B = 23, 5  # five rounds, the last one partial (3 lanes)


def _sides(side):
    """(src, dst, src_idx, dst_idx, move kwargs) for one kind of move: the
    raw pytree, a host store on either side, a tiered arena as destination,
    and chunked staging on either side."""
    from repro.store.arena import ArenaStore
    from repro.store.host_store import HostStore

    rng = np.random.default_rng(7)
    big = {"w": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)}
    small = {"w": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)}
    load = (jnp.asarray(rng.permutation(64)[:_K], jnp.int32),
            jnp.asarray(rng.permutation(32)[:_K], jnp.int32))
    back = load[::-1]
    if side == "raw":
        return big, small, *load, {}
    if side in ("host_fp32", "host_int8"):
        return HostStore.create(big, side[5:]), small, *load, {}
    if side == "host_int8_dst":
        return small, HostStore.create(big, "int8"), *back, {}
    if side == "arena_int8_dst":
        return big, ArenaStore.create(small, 8, "int8"), *load, {}
    if side == "host_to_arena_int8":  # the encoded verbatim tail path
        return HostStore.create(big, "int8"), ArenaStore.create(small, 8, "int8"), *load, {}
    if side == "chunked_src":
        return big, small, *load, {"src_chunk_rows": 8}
    if side == "chunked_dst":
        return small, big, *back, {"dst_chunk_rows": 8}
    raise ValueError(side)


_SIDES = ["raw", "host_fp32", "host_int8", "host_int8_dst", "arena_int8_dst",
          "host_to_arena_int8", "chunked_src", "chunked_dst"]


@pytest.mark.parametrize("n", [0, 1, _B - 1, _B, _B + 1, _K])
@pytest.mark.parametrize("side", _SIDES)
def test_live_lanes_move_is_bit_identical_to_static_rounds(side, n):
    """With every lane at or past ``n`` inactive, stopping after the rounds
    that reach ``n`` changes no bit of the destination, whatever the sides."""
    src, dst, si, di, kw = _sides(side)
    lane = jnp.arange(_K)
    active = (lane < n) & (lane % 4 != 2)  # holes among the live lanes too
    static = transmitter.move_rows(src, dst, si, di, active, buffer_rows=_B, **kw)
    live = jax.jit(
        lambda m: transmitter.move_rows(src, dst, si, di, active, buffer_rows=_B,
                                        live_lanes=m, **kw)
    )(jnp.int32(n))
    want, got = jax.tree_util.tree_leaves(static), jax.tree_util.tree_leaves(live)
    assert len(want) == len(got)
    for a, b in zip(want, got):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n,rounds", [(0, 0), (1, 1), (_B, 1), (_B + 1, 2), (_K, 5),
                                      (10 * _K, 5)])
def test_live_rounds_is_the_ceiling_capped_at_the_static_count(n, rounds):
    assert int(transmitter.live_rounds(_K, _B, jnp.int32(n))) == rounds
    assert int(transmitter.live_rounds(_K, 64, jnp.int32(n))) == min(rounds, 1)


def _loops(jaxpr):
    """Every ``while`` / ``scan`` equation, nested jaxprs included."""
    from jax.extend import core as jcore

    for e in jaxpr.eqns:
        if e.primitive.name in ("while", "scan"):
            yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _loops(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _loops(sub)


def test_live_lanes_loop_has_a_traced_trip_count_and_one_round_none():
    src, dst, si, di, _ = _sides("raw")
    active = jnp.arange(_K) < 3

    def move(m, buffer_rows):
        return transmitter.move_rows(src, dst, si, di, active, buffer_rows=buffer_rows,
                                     live_lanes=m)

    loops = list(_loops(jax.make_jaxpr(lambda m: move(m, _B))(jnp.int32(3)).jaxpr))
    assert [e.primitive.name for e in loops] == ["while"]
    assert loops[0].params["cond_jaxpr"].out_avals[0].shape == ()
    # one round: no loop at all, as without ``live_lanes``
    assert not list(_loops(jax.make_jaxpr(lambda m: move(m, 64))(jnp.int32(3)).jaxpr))
