"""The general traffic generator: seeded, repeatable, within bounds."""

import json
import os
import struct

import pytest

from benchmarks import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 32768


def _mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-closed32", "doc-closed16"])
def test_same_seed_same_requests_other_seed_other_requests(name):
    mix = _mix(name)
    def batch(seed):
        return [traffic.request(name, mix, seed, VOCAB, c, i)
                for c in range(4) for i in range(8)]
    assert batch(7) == batch(7)
    assert batch(7) != batch(8)
    # a request does not depend on which requests were drawn before it
    assert traffic.request(name, mix, 7, VOCAB, 3, 5) == batch(7)[3 * 8 + 5]


@pytest.mark.parametrize("name", ["chat-closed32", "doc-closed16"])
def test_requests_stay_inside_their_class_and_the_vocabulary(name):
    mix = _mix(name)
    classes = {c["name"]: c for c in mix["prompt_classes"]}
    out_lo, out_hi = traffic.dist_bounds(mix["output"])
    seen = set()
    for i in range(300):
        r = traffic.request(name, mix, 1, VOCAB, i % mix["clients"], i)
        lo, hi = traffic.dist_bounds(classes[r["class"]]["length"])
        assert lo <= len(r["prompt"]) <= hi
        assert out_lo <= r["max_tokens"] <= out_hi
        assert all(0 <= t < VOCAB for t in r["prompt"])
        seen.add(r["class"])
    assert seen == set(classes)


def test_a_client_keeps_its_class_so_the_mix_in_flight_is_fixed():
    for name, want in (("chat-closed32", {"p64": 17, "p128": 9, "p256": 6}),
                       ("doc-closed16", {"p1024": 10, "p2048": 6})):
        mix = _mix(name)
        for seed in (1, 2):
            for c in range(mix["clients"]):
                classes = {traffic.request(name, mix, seed, VOCAB, c, i)
                           ["class"] for i in range(6)}
                assert len(classes) == 1  # a client keeps its class
            counts = {}
            for c in range(mix["clients"]):
                k = traffic.request(name, mix, seed, VOCAB, c, 0)["class"]
                counts[k] = counts.get(k, 0) + 1
            assert counts == want  # the same for every seed


def test_a_deck_that_cannot_hold_the_weights_is_refused():
    with pytest.raises(ValueError, match="class_deck"):
        traffic.request("m", dict(_mix("chat-closed32"), class_deck=3),
                        1, VOCAB, 0, 0)
    with pytest.raises(ValueError, match="unknown length distribution"):
        traffic.draw({"dist": "fixed", "value": 4}, None)


def test_the_client_knows_the_closed_loop_only(tmp_path):
    from benchmarks import client

    spec = {"url": "http://127.0.0.1:1", "mix_name": "m", "seed": 1,
            "vocab": VOCAB, "mix": dict(_mix("chat-closed32"), loop="open")}
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(SystemExit, match="unknown loop"):
        client.main(["client.py", str(path)])


def test_lognormal_output_has_its_median_and_its_clip():
    mix = _mix("chat-closed32")
    outs = sorted(traffic.request("chat-closed32", mix, 3, VOCAB, 0, i)
                  ["max_tokens"] for i in range(4000))
    assert outs[0] == 32 and outs[-1] == 128
    assert 58 <= outs[len(outs) // 2] <= 70


@pytest.mark.parametrize("name, programs", [("chat-closed32", 24),
                                            ("doc-closed16", 8)])
def test_warmup_plan_visits_every_program_of_the_mix(name, programs):
    """Simulate the engine's bucketing: prefill pads to whole blocks,
    decode step j of a request runs at width blocks(n + j)."""
    mix, bs = _mix(name), 16
    plan = traffic.warmup_requests(mix, VOCAB, bs)
    prefill, widths = set(), set()
    for w in plan:
        n = len(w["prompt"])
        prefill.add(-(-n // bs))
        widths.update(-(-(n + j) // bs) for j in range(1, w["max_tokens"]))
    assert widths == traffic.decode_widths(mix, bs)
    # every request the mix can draw stays inside what was warmed
    out_hi = traffic.dist_bounds(mix["output"])[1]
    for cls in mix["prompt_classes"]:
        lo, hi = traffic.dist_bounds(cls["length"])
        for n in range(lo, hi + 1):
            assert -(-n // bs) in prefill
            assert {-(-(n + j) // bs) for j in range(1, out_hi)} <= widths
    assert len(prefill) + len(widths) == programs
    # and it is cheap: far fewer decode steps than one full-length
    # request per class bound
    steps = sum(w["max_tokens"] - 1 for w in plan)
    assert steps <= len(widths) * bs


def test_token_records_are_seeded_learnable_progressions():
    mix = {"B": 2, "T": 100,
           "records": {"alphabet": 64, "max_stride": 6, "n_batches": 3}}
    a = list(traffic.token_records("m", mix, 1, VOCAB))
    assert a == list(traffic.token_records("m", mix, 1, VOCAB))
    assert a != list(traffic.token_records("m", mix, 2, VOCAB))
    assert len(a) == 6 and all(len(r) == 101 * 4 for r in a)
    ids = struct.unpack("<101i", a[0])
    alphabet = [i * (VOCAB // 64) + 7 for i in range(64)]
    pos = [alphabet.index(t) for t in ids]
    stride = (pos[1] - pos[0]) % 64
    assert 1 <= stride <= 6
    assert all((q - p) % 64 == stride for p, q in zip(pos, pos[1:]))
