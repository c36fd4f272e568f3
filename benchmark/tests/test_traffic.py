"""The parameter draw: domains per TPC-H 2.4, no repeats, set by the
seed, never the warm-up's set."""

import json
import os

import pytest

import traffic
from conftest import HERE

MIX = {"loop": "closed", "clients": 1, "templates": ["q6", "q1"]}


def test_domains_are_tpch_2_4():
    q6 = traffic.load_template("q6").domain()
    assert len(q6) == 5 * 8 * 2
    assert {p["year"] for p in q6} == set(range(1993, 1998))
    assert {p["discount"] for p in q6} == set(range(2, 10))
    assert {p["quantity"] for p in q6} == {24, 25}
    q1 = traffic.load_template("q1").domain()
    assert [p["delta"] for p in q1] == list(range(60, 121))
    q3 = traffic.load_template("q3").domain()
    assert len(q3) == 5 * 31
    assert {p["day"] for p in q3} == set(range(1, 32))
    assert {p["segment"] for p in q3} == {
        "AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"}


@pytest.mark.parametrize("name", ["q6", "q1", "q3"])
def test_validation_set_is_in_the_domain_and_never_drawn(name):
    t = traffic.load_template(name)
    assert t.VALIDATION in t.domain()
    pool = traffic.draws(t, 2**31 + 11)
    assert t.VALIDATION not in pool
    assert len(pool) == len(t.domain()) - 1
    assert len({tuple(sorted(p.items())) for p in pool}) == len(pool)


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_slot_values_recur_as_late_as_the_domain_allows(seed):
    q3 = traffic.draws(traffic.load_template("q3"), seed)
    # four segments besides the warm-up's BUILDING, then all five
    assert len({p["segment"] for p in q3[:4]}) == 4
    assert "BUILDING" not in {p["segment"] for p in q3[:4]}
    assert len({p["day"] for p in q3[:30]}) == 30
    assert 15 not in {p["day"] for p in q3[:30]}
    q1 = traffic.draws(traffic.load_template("q1"), seed)
    assert len({p["delta"] for p in q1}) == 60


def test_a_mix_draws_its_listed_parameter_sets_first():
    mix = traffic.load_mix("join")
    listed = mix["parameters"]["q3"]
    assert len(listed) >= 12
    for seed in (3, 2147483933):
        got = [next(s)[1] for s in [traffic.Stream(mix, seed, "tpch.sf10")]
               for _ in range(len(listed) + 3)]
        assert sorted(map(str, got[:len(listed)])) == sorted(map(str, listed))
        assert all(p not in listed for p in got[len(listed):])
        # the warm-up's segment comes only after the four others
        assert len({p["segment"] for p in got[:4]}) == 4
        assert "BUILDING" not in {p["segment"] for p in got[:4]}


def test_a_listed_set_outside_the_domain_is_refused():
    q3 = traffic.load_template("q3")
    with pytest.raises(ValueError, match="not a parameter set"):
        traffic.draws(q3, 1, [{"segment": "BUILDING", "day": 32}])
    with pytest.raises(ValueError, match="not a parameter set"):
        traffic.draws(q3, 1, [q3.VALIDATION])


def test_render_q6_discount_band_and_literals():
    t = traffic.load_template("q6")
    sql = t.render({"year": 1995, "discount": 9, "quantity": 25}, "tpch.x")
    assert "BETWEEN 0.08 AND 0.10" in sql
    assert "DATE '1995-01-01'" in sql and "l_quantity < 25" in sql
    assert "tpch.x.lineitem" in sql


def test_stream_is_set_by_the_seed_and_never_repeats():
    one = traffic.Stream(MIX, 2147483659, "tpch.sf10")
    two = traffic.Stream(MIX, 2147483659, "tpch.sf10")
    other = traffic.Stream(MIX, 2147483660, "tpch.sf10")
    first = [next(one) for _ in range(40)]
    again = [next(two) for _ in range(40)]
    third = [next(other) for _ in range(40)]
    assert [s[2] for s in first] == [s[2] for s in again]
    assert [s[2] for s in first] != [s[2] for s in third]
    texts = [s[2] for s in first]
    assert len(set(texts)) == len(texts)
    # every seed sends the same sequence of templates
    assert [s[0].NAME for s in first] == ["q6", "q1"] * 20
    assert [s[0].NAME for s in third] == ["q6", "q1"] * 20


@pytest.mark.parametrize("clients,each", [(1, 60), (3, 20)])
def test_stream_has_no_round_left_when_a_domain_is_spent(clients, each):
    """q1's 60 sets (TPC-H 2.4.1.3, the validation set left out) end the
    rounds of q6 + q1 though q6 has 79; three clients take 20 each."""
    mix = dict(MIX, clients=clients)
    for c in range(clients):
        s = traffic.Stream(mix, 2147483659, "tpch.sf10", c)
        rounds = 0
        while s.round_left():
            assert s.spent() is None
            for _ in mix["templates"]:
                next(s)
            rounds += 1
        assert rounds == each
        assert s.spent() == ("q1", each)


def test_next_past_the_domain_still_fails():
    s = traffic.Stream({"loop": "closed", "clients": 1,
                        "templates": ["q1"]}, 1, "tpch.tiny")
    for _ in range(60):
        assert s.round_left()
        next(s)
    assert not s.round_left()
    with pytest.raises(RuntimeError, match="domain exhausted"):
        next(s)


@pytest.mark.parametrize("mix", ["scan", "join"])
@pytest.mark.parametrize("seed", [7, 2147483659, 3000000019])
def test_first_40_statements_are_the_accepted_benchmarks(mix, seed):
    """Byte for byte the texts that traffic.py of the benchmark accepted
    at PR 30 yields (tests/data/golden_statements.json, written from
    that file): the window rule does not touch the order of draws."""
    with open(os.path.join(HERE, "data", "golden_statements.json")) as f:
        golden = json.load(f)[f"{mix}:{seed}"]
    s = traffic.Stream(traffic.load_mix(mix), seed, "tpch.sf10")
    assert [next(s)[2] for _ in range(40)] == golden


def test_clients_take_disjoint_statements():
    mix = dict(MIX, clients=3)
    texts = []
    for c in range(3):
        s = traffic.Stream(mix, 99, "tpch.sf10", c)
        texts += [next(s)[2] for _ in range(12)]
    assert len(set(texts)) == len(texts)


def test_unknown_loop_kind_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "open.json").write_text(
        '{"loop": "open", "clients": 1, "templates": ["q6"]}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="not implemented"):
        traffic.load_mix("open")
