"""The closed-loop generator: the same work for every seed, in another
order, and the accounting of the loop that drives it."""
import json

import numpy as np

from conftest import BENCH, TINY_MIX

import loadgen


# a document-chat mix: long prompts, few rounds in a window
LONGCTX = {"clients": 32, "slots": 32, "prefill_chunk": 256,
           "admit_chunks_per_step": 4, "max_len": 8704,
           "prompt": {"median": 2048, "sigma": 0.693, "min": 512,
                      "max": 8192},
           "output": {"median": 128, "sigma": 0.693, "min": 32, "max": 512},
           "check_tokens": 400, "check_requests": 6}


def mix(name):
    if name == "longctx":
        return dict(LONGCTX)
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_requests():
    a = loadgen.ClosedLoop(mix("chat"), 151936, 2 ** 33 + 5)
    b = loadgen.ClosedLoop(mix("chat"), 151936, 2 ** 33 + 5)
    for c, j in [(0, 0), (5, 3), (31, 7)]:
        pa, oa = a.request(c, j)
        pb, ob = b.request(c, j)
        assert oa == ob and np.array_equal(pa, pb)


def test_seeds_differ_in_high_bits():
    a = loadgen.ClosedLoop(mix("chat"), 151936, 5)
    b = loadgen.ClosedLoop(mix("chat"), 151936, 2 ** 33 + 5)
    assert not np.array_equal(a.request(0, 0)[0][:8], b.request(0, 0)[0][:8])


def test_every_round_serves_the_same_lengths():
    m = mix("longctx")
    want_p = sorted(loadgen.quantile_lengths(m["prompt"], m["clients"]))
    want_o = sorted(loadgen.quantile_lengths(m["output"], m["clients"]))
    for seed in (1, 2, 2 ** 34 + 3):
        g = loadgen.ClosedLoop(m, 75776, seed)
        for j in range(3):
            reqs = [g.request(c, j) for c in range(m["clients"])]
            assert sorted(len(p) for p, _ in reqs) == want_p
            assert sorted(o for _, o in reqs) == want_o


def test_lengths_follow_the_mix():
    m = mix("chat")
    p = loadgen.quantile_lengths(m["prompt"], 32)
    assert p.min() >= 64 and p.max() == 1024 and np.median(p) in (256, 257)
    g = loadgen.ClosedLoop(m, 151936, 1)
    assert g.max_cache_rows(m["prefill_chunk"]) <= m["max_len"]
    g = loadgen.ClosedLoop(mix("longctx"), 75776, 1)
    assert g.max_cache_rows(256) <= mix("longctx")["max_len"]


def test_closed_loop_accounting(run_tiny_cell):
    """One request in flight per client, always; every request sent in a
    phase is either ended in it or still in flight at its end."""
    sc = run_tiny_cell
    clients = TINY_MIX["clients"]
    assert all(c.req is not None for c in sc.clients)
    before = {c.idx: c.j for c in sc.clients}
    seen0 = sum(c.seen for c in sc.clients)
    ph = sc.measure(1.0)
    assert len(sc.clients) == clients
    sent_by_client = sum(c.j - before[c.idx] for c in sc.clients)
    assert ph.sent == sent_by_client == len(ph.done)
    # tokens of the requests that ended, plus those in flight at the end,
    # less what the requests in flight at the start had already shown
    assert ph.tokens == sum(len(o) for _, o in ph.done) + sum(
        c.seen for c in sc.clients) - seen0
    assert len(ph.ttft) <= ph.sent + clients


def test_fixed_order_is_the_same_for_every_seed():
    m = dict(mix("longctx"), order="fixed")
    a = loadgen.ClosedLoop(m, 75776, 1)
    b = loadgen.ClosedLoop(m, 75776, 2 ** 34 + 3)
    for c, j in [(0, 0), (7, 1), (31, 4)]:
        (pa, oa), (pb, ob) = a.request(c, j), b.request(c, j)
        assert len(pa) == len(pb) and oa == ob
        assert not np.array_equal(pa, pb)     # the ids still follow the seed
    seeded = loadgen.ClosedLoop(dict(m, order="seeded"), 75776, 1)
    assert [len(seeded.request(c, 0)[0]) for c in range(32)] != \
        [len(a.request(c, 0)[0]) for c in range(32)]
