"""The reference against Cypher's semantics spelled out row by row, a
relationship taken twice included; the data's shape; the control's copy."""
import numpy as np
import pytest

from generators import fof

SIZES = {"people": 60, "friends_per_person": 5}


def loop_reference(data, start, hops):
    """Every walk of ``hops`` relationships, none taken twice, by
    recursion over (person, slot) pairs."""
    ends = set()

    def walk(at, used):
        if len(used) == hops:
            ends.add(at)
            return
        for slot, nxt in enumerate(data.friends[at]):
            rel = (at, slot)
            alive = data.alive is None or data.alive[at, slot]
            if alive and rel not in used:
                walk(int(nxt), used + [rel])

    walk(start, [])
    return len(ends)


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_reference_equals_the_loop(seed):
    data = fof.make_data(SIZES, seed)
    # p3's only way to its first friend b, and b's only way on, is back:
    # a->b->a->b would take a->b twice, so b is no end of three hops
    # unless another walk reaches it
    b = int(data.friends[3, 0])
    data.friends[b, :] = 3
    data.friends[3, 1] = data.friends[3, 0]          # a pair that repeats
    for d in (data, fof.stale_copy(data, 0.2)):
        for i in (3, b, 11, 59):
            got = fof.reference(d, ["fof2", "fof3"], {"name": f"p{i}"})
            assert got == {"fof2": [{"fof": loop_reference(d, i, 2)}],
                           "fof3": [{"fof": loop_reference(d, i, 3)}]}
    assert fof.reference(data, ["fof2"], {"name": "p60"}) \
        == {"fof2": [{"fof": 0}]}
    assert fof.reference(data, ["fof2"], {"name": "p03"}) \
        == {"fof2": [{"fof": 0}]}


def test_uniqueness_bites_at_three_hops():
    data = fof.Data(np.array([[1], [0]], dtype=np.int64))
    assert fof.reference(data, ["fof2", "fof3"], {"name": "p0"}) \
        == {"fof2": [{"fof": 1}], "fof3": [{"fof": 0}]}


def test_same_seed_same_data_and_large_seeds_differ():
    a, b = fof.make_data(SIZES, 2**31 + 5), fof.make_data(SIZES, 2**31 + 5)
    assert (a.friends == b.friends).all()
    assert (a.friends != fof.make_data(SIZES, 2**31 + 6).friends).any()
    assert a.friends.shape == (60, 5)
    assert (a.friends != np.arange(60)[:, None]).all()      # no self-loop
    assert a.friends.min() == 0 and a.friends.max() == 59
    binds = fof.bindings(a, {"kind": "people", "count": 60},
                         np.random.RandomState(1))
    assert sorted(int(p["name"][1:]) for p in binds) == list(range(60))


def test_stale_copy_answers_differently():
    data = fof.make_data({"people": 2000, "friends_per_person": 50}, 11)
    stale = fof.stale_copy(data, 0.01)
    assert (~stale.alive).sum() == 1000
    assert fof.reference(data, ["fof2"], {"name": "p5"}) \
        != fof.reference(stale, ["fof2"], {"name": "p5"})
