"""Unit + property tests for gptr/group/team (paper §III, §IV.B.1/2/4)."""

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (DART_GPTR_NULL, GlobalPtr, DartGroup, FreeListTeamList,
                        Team, TeamList, TeamListFullError, TeamPartition,
                        dart_group_addmember, dart_group_delmember,
                        dart_group_init, dart_group_intersect,
                        dart_group_split, dart_group_union, group_from_units)
from repro.core.gptr import ADDR_MAX, FLAG_COLLECTIVE, SEG_MAX, UNIT_MAX


# ---------------------------------------------------------------- gptr ----

gptrs = st.builds(
    GlobalPtr,
    unitid=st.integers(0, UNIT_MAX),
    segid=st.integers(0, SEG_MAX),
    flags=st.integers(0, (1 << 16) - 1),
    addr=st.integers(0, ADDR_MAX),
)


@given(gptrs)
def test_gptr_pack_unpack_roundtrip(g):
    assert GlobalPtr.unpack(g.pack()) == g


@given(gptrs)
def test_gptr_words_roundtrip(g):
    assert GlobalPtr.from_words(g.to_words()) == g


@given(gptrs, st.integers(0, 1 << 20))
def test_gptr_incaddr(g, n):
    if g.addr + n > ADDR_MAX:
        with pytest.raises(ValueError):
            g.incaddr(n)
    else:
        g2 = g.incaddr(n)
        assert g2.addr == g.addr + n
        assert (g2.unitid, g2.segid, g2.flags) == (g.unitid, g.segid, g.flags)


@given(gptrs, st.integers(0, 1 << 20))
def test_gptr_decaddr_and_sub_int(g, n):
    """decaddr / ``- int`` mirror incaddr with a lower-bound check."""
    if n > g.addr:
        with pytest.raises(ValueError):
            g.decaddr(n)
        with pytest.raises(ValueError):
            g - n
    else:
        g2 = g.decaddr(n)
        assert g2.addr == g.addr - n
        assert (g2.unitid, g2.segid, g2.flags) == (g.unitid, g.segid, g.flags)
        assert (g - n) == g2


@given(gptrs, st.integers(0, 1 << 20))
def test_gptr_inc_dec_roundtrip(g, n):
    if g.addr + n <= ADDR_MAX:
        assert (g + n) - n == g
        assert (g + n).decaddr(n) == g


def test_gptr_decaddr_edge_cases():
    g = GlobalPtr(unitid=0, segid=0, flags=0, addr=128)
    assert g.decaddr(128).addr == 0            # down to exactly zero
    assert g.decaddr(0) == g
    assert g.decaddr(-64).addr == 192          # negative = incaddr
    with pytest.raises(ValueError):
        g.decaddr(129)                         # below the pool base


def test_gptr_addrdiff_same_segment():
    g = GlobalPtr(unitid=1, segid=3, flags=FLAG_COLLECTIVE, addr=256)
    assert g.addrdiff(g) == 0
    assert (g + 128) - g == 128
    assert g - (g + 128) == -128               # signed distance
    # collective pointers: unit-independent offsets (aligned & symmetric)
    assert g.setunit(5) - g == 0
    assert (g.setunit(5) + 64) - g == 64


def test_gptr_addrdiff_rejects_mismatched_segments():
    coll = GlobalPtr(unitid=0, segid=2, flags=FLAG_COLLECTIVE, addr=128)
    with pytest.raises(ValueError):
        coll.addrdiff(GlobalPtr(unitid=0, segid=3, flags=FLAG_COLLECTIVE,
                                addr=0))       # different segment
    with pytest.raises(ValueError):
        coll.addrdiff(GlobalPtr(unitid=0, segid=2, flags=0, addr=0))
    # non-collective: offsets are per-unit partitions — unit must match
    nc0 = GlobalPtr(unitid=0, segid=0, flags=0, addr=256)
    nc1 = GlobalPtr(unitid=1, segid=0, flags=0, addr=128)
    with pytest.raises(ValueError):
        nc0 - nc1
    assert nc0 - (nc0 + 128) == -128


def test_gptr_is_128_bits():
    g = GlobalPtr(unitid=UNIT_MAX, segid=SEG_MAX, flags=(1 << 16) - 1,
                  addr=ADDR_MAX)
    assert g.pack() == (1 << 128) - 1
    assert DART_GPTR_NULL.pack() == 0


def test_gptr_flags_semantics():
    g = GlobalPtr(unitid=3, segid=2, flags=FLAG_COLLECTIVE, addr=128)
    assert g.is_collective
    assert g.setunit(7).unitid == 7
    assert not DART_GPTR_NULL.is_collective


def test_gptr_range_validation():
    with pytest.raises(ValueError):
        GlobalPtr(unitid=-1, segid=0, flags=0, addr=0)
    with pytest.raises(ValueError):
        GlobalPtr(unitid=0, segid=SEG_MAX + 1, flags=0, addr=0)


# --------------------------------------------------------------- group ----

unit_lists = st.lists(st.integers(0, 1000), max_size=40)


@given(unit_lists, unit_lists)
def test_group_union_is_sorted_dedup_set_union(a, b):
    """Paper §IV.B.1: dart_group_union merge-sorts its inputs."""
    ga, gb = group_from_units(a), group_from_units(b)
    gu = dart_group_union(ga, gb)
    assert list(gu.members) == sorted(set(a) | set(b))


@given(unit_lists)
def test_group_addmember_order_independent(units):
    """Any insertion order yields the ascending-ordered group (Fig. 2)."""
    import random
    g1 = group_from_units(units)
    shuffled = list(units)
    random.Random(0).shuffle(shuffled)
    g2 = group_from_units(shuffled)
    assert g1 == g2
    assert list(g1.members) == sorted(set(units))


@given(unit_lists, unit_lists)
def test_group_intersect(a, b):
    gi = dart_group_intersect(group_from_units(a), group_from_units(b))
    assert list(gi.members) == sorted(set(a) & set(b))


@given(unit_lists, st.integers(1, 8))
def test_group_split_partitions(units, n):
    g = group_from_units(units)
    parts = dart_group_split(g, n)
    assert len(parts) == n
    recombined = [u for p in parts for u in p.members]
    assert recombined == list(g.members)          # contiguous, order kept
    sizes = [p.size() for p in parts]
    assert max(sizes) - min(sizes) <= 1           # balanced


def test_group_invariant_rejects_disorder():
    with pytest.raises(ValueError):
        DartGroup((3, 1))
    with pytest.raises(ValueError):
        DartGroup((1, 1))


def test_group_membership():
    g = group_from_units([5, 1, 9])
    assert g.ismember(5) and g.ismember(1) and g.ismember(9)
    assert not g.ismember(2)
    assert dart_group_delmember(g, 5).members == (1, 9)


# ---------------------------------------------------------------- team ----

@pytest.mark.parametrize("cls", [TeamList, FreeListTeamList])
def test_teamlist_alloc_reuse(cls):
    """Paper §IV.B.2: slots are reused after team destruction."""
    tl = cls(capacity=4)
    s0 = tl.alloc(100)
    s1 = tl.alloc(101)
    assert (s0, s1) == (0, 1)
    assert tl.lookup(101) == 1
    tl.free(100)
    assert tl.alloc(102) == 0          # freed slot is recycled
    tl.alloc(103); tl.alloc(104)
    with pytest.raises(TeamListFullError):
        tl.alloc(105)


@pytest.mark.parametrize("cls", [TeamList, FreeListTeamList])
def test_teamlist_lowest_slot_first(cls):
    tl = cls(capacity=8)
    for t in range(5):
        tl.alloc(t)
    tl.free(1); tl.free(3)
    assert tl.alloc(10) == 1           # deterministic: lowest free slot
    assert tl.alloc(11) == 3


@given(st.lists(st.integers(0, 500), min_size=1, max_size=30, unique=True))
def test_teamlist_impls_agree(ops):
    """The O(1) free-list variant (§VI) matches the paper allocator."""
    a, b = TeamList(64), FreeListTeamList(64)
    for i, t in enumerate(ops):
        assert a.alloc(t) == b.alloc(t)
        if i % 3 == 2:
            a.free(t); b.free(t)
    assert a.live() == b.live()


def test_team_unit_translation():
    """Paper §IV.B.4: absolute <-> relative unit translation."""
    g = group_from_units([2, 5, 11, 30])
    team = Team(teamid=7, group=g, slot=3)
    assert [team.myid(u) for u in (2, 5, 11, 30)] == [0, 1, 2, 3]
    assert team.myid(4) == -1
    assert [team.unit_at(r) for r in range(4)] == [2, 5, 11, 30]


def test_team_partition_validation():
    g1, g2 = group_from_units([0, 1]), group_from_units([2, 3])
    t1 = Team(teamid=1, group=g1, slot=0)
    t2 = Team(teamid=2, group=g2, slot=1)
    p = TeamPartition((t1, t2))
    assert p.axis_index_groups == [[0, 1], [2, 3]]
    assert p.team_of(3) is t2
    bad = Team(teamid=3, group=group_from_units([4, 5, 6]), slot=2)
    with pytest.raises(ValueError):
        TeamPartition((t1, bad))
