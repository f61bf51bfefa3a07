"""The generator and the request accounting: fast, CPU only, no JAX."""
import collections
import json
import os

import numpy as np
import pytest

from chipbench.drivers import serve_open_loop as sol
from chipbench.traffic import gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = ["chat-shared", "longgen-overload"]


def mix_of(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seeds", [(11, 3000000019), (5, 2147483659)])
def test_two_seeds_offer_the_same_work_in_another_order(name, seeds):
    mix = mix_of(name)
    a, b = (gen.stream(mix, s, 1000) for s in seeds)
    seen, seen_end = [], 0.0
    for _ in range(3):
        ra, rb = next(a), next(b)
        assert len(ra) == len(rb) == mix["block"]
        # every block holds the distribution's mid-quantiles, whatever the order
        assert sorted(r.prompt_tokens - mix["prefix_tokens"] for r in ra) == \
            sorted(gen.mid_quantiles(mix["dist"], *mix["tail_tokens"], mix["block"]))
        assert sorted(r.output_tokens for r in ra) == \
            sorted(gen.mid_quantiles(mix["dist"], *mix["output_tokens"], mix["block"]))
        assert collections.Counter(r.tenant for r in ra) == \
            collections.Counter(np.arange(mix["block"]) % mix["tenants"])
        # two seeds: the same lengths, tenants and gaps, in another order
        fields = [lambda r: r.prompt_tokens, lambda r: r.output_tokens]
        if mix["tenants"] > 1:
            fields.append(lambda r: r.tenant)
        for what in fields:
            assert sorted(map(what, ra)) == sorted(map(what, rb))
            assert list(map(what, ra)) != list(map(what, rb))
        t0 = seen_end
        ga, gb = (np.diff([t0] + [r.due_s for r in rs]) for rs in (ra, rb))
        np.testing.assert_allclose(np.sort(ga), np.sort(gb), atol=1e-9)
        if mix["arrivals"]["kind"] == "poisson":
            assert not np.allclose(ga, gb)
        tot_a, tot_b = gen.block_totals(ra), gen.block_totals(rb)
        assert tot_a["prompt_tokens"] == tot_b["prompt_tokens"]
        assert tot_a["output_tokens"] == tot_b["output_tokens"]
        assert ra[-1].due_s == pytest.approx(rb[-1].due_s, abs=1e-9)
        seen.append([r.prompt_tokens for r in ra])
        seen_end = ra[-1].due_s
    # the blocks differ from each other in order
    assert seen[0] != seen[1] and seen[1] != seen[2]


def test_gaps_of_a_block_are_the_same_multiset_and_sum_to_block_over_rate():
    mix = mix_of("chat-shared")
    rate, n = mix["arrivals"]["rate_per_s"], mix["block"]
    for seed in (5, 2147483777):
        t0 = 0.0
        for reqs in [next(s) for s in [gen.stream(mix, seed, 1000)] * 1]:
            due = np.array([t0] + [r.due_s for r in reqs])
            gaps = np.sort(np.diff(due))
            np.testing.assert_allclose(gaps, np.sort(gen.gap_quantiles(rate, n)))
            assert abs(gaps.sum() - n / rate) < 1e-9


def test_same_seed_gives_the_same_requests():
    mix = mix_of("chat-shared")
    a, b = next(gen.stream(mix, 2 ** 31 + 9, 500)), next(gen.stream(mix, 2 ** 31 + 9, 500))
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s


def test_tenants_share_their_system_prompt():
    mix = mix_of("chat-shared")
    reqs = next(gen.stream(mix, 3, 1000))
    by_tenant = collections.defaultdict(list)
    for r in reqs:
        by_tenant[r.tenant].append(r.prompt[:mix["prefix_tokens"]])
    assert len(by_tenant) == mix["tenants"]
    for rows in by_tenant.values():
        assert len(rows) == mix["block"] // mix["tenants"]
        assert all(np.array_equal(rows[0], r) for r in rows)


def test_staggered_block_leaves_row_k_with_its_share():
    mix = mix_of("longgen-overload")
    first = next(gen.stream(mix, 17, 1000))
    rows = gen.stagger(first, 17, 1000)
    n = len(rows)
    assert sorted(s.index for s in rows) == [r.index for r in first]
    by_index = {r.index: r for r in first}
    for k, s in enumerate(rows):
        r = by_index[s.index]
        left = s.max_new / r.output_tokens
        assert abs(left - (1 - k / n)) <= 1.0 / r.output_tokens + 1e-9
        assert s.prompt.size == r.prompt.size + r.output_tokens - s.max_new
        assert s.max_new >= 1
    total = sum(r.prompt.size + r.max_new for r in rows)
    e = mix["engine"]
    assert total < (e["num_pages"] - 1) * e["page_size"]
    # another seed opens on the same depths and the same total context
    other = gen.stagger(next(gen.stream(mix, 3000000019, 1000)), 3000000019, 1000)
    assert [(s.output_tokens, s.max_new) for s in other] == \
        [(s.output_tokens, s.max_new) for s in rows]
    assert sum(s.prompt.size for s in other) == sum(s.prompt.size for s in rows)
    assert [s.index for s in other] != [s.index for s in rows]


def test_covering_tails_meet_every_reachable_pair():
    for name in MIXES:
        mix = mix_of(name)
        e = mix["engine"]
        for shared in ([False, True] if mix["prefix_tokens"] else [False]):
            tails = gen.covering_tails(mix, e, shared)
            have = mix["prefix_tokens"] if shared else 0
            met = set()
            for t in tails:
                assert mix["tail_tokens"][0] <= t <= mix["tail_tokens"][1]
                met |= set(gen.chunk_pairs(mix["prefix_tokens"] + t, have,
                                           e["page_size"], e["prefill_chunk"],
                                           e["max_len"]))
            for t in range(mix["tail_tokens"][0], mix["tail_tokens"][1] + 1):
                assert set(gen.chunk_pairs(mix["prefix_tokens"] + t, have,
                                           e["page_size"], e["prefill_chunk"],
                                           e["max_len"])) <= met
            assert len(met) <= 28


def test_chunk_pairs_follow_the_engines_two_rounding_rules():
    # 1,024 cached, 300 to go: 256 at a context of 16 pages, then 44 -> one
    # page of width at a context of 20 pages, bucketed up to 32
    assert gen.chunk_pairs(1324, 1024, 64, 256, 4096) == [(16, 256), (32, 64)]
    assert gen.chunk_pairs(130, 0, 64, 256, 4096) == [(0, 192)]


# ---- a request's accounting on a scripted engine ----

class FakeHandle:
    def __init__(self, prompt, n):
        self.prompt = np.asarray(prompt).reshape(1, -1)
        self.tokens, self.done, self.slot, self.want = [], False, None, n


class FakeSched:
    """Admits everything at once; every step gives each request one token."""

    def __init__(self, clock):
        self.clock, self.hs, self.last_plan = clock, [], None
        self.engine = self
        self.cache = self
        self.allocator = self
        self.num_used = 0

    def submit(self, prompt, max_new_tokens):
        h = FakeHandle(prompt, max_new_tokens)
        self.hs.append(h)
        return h

    def step(self):
        self.clock.t += 0.1            # a step takes 100 ms
        for h in self.hs:
            if not h.done:
                h.slot = 0
                h.tokens.append(7)
                h.done = len(h.tokens) >= h.want
        return any(not h.done for h in self.hs)


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


def test_request_accounting_on_a_scripted_engine():
    clock = Clock()
    loop = sol.Loop(FakeSched(clock), clock=clock)
    mk = lambda due, n, counted: sol.Live(
        gen.Request(0, 0, 0, np.arange(5, dtype=np.int32), n, due, 5, n), due, counted)
    early = mk(-1.0, 3, False)          # the pre-roll: not counted
    a, b = mk(0.05, 3, True), mk(0.32, 30, True)
    never = mk(0.95, 2, True)           # due inside, never handed over
    pending = [early, a, b]
    t_open, t_close = 0.0, 1.0
    while clock() < t_close:
        while pending and pending[0].due <= clock():
            loop.submit(pending.pop(0))
        loop.step() if loop.open else setattr(clock, "t", clock.t + 0.01)
    due_in = [lv for lv in loop.lives if lv.counted]
    attempted, failed, ttft, gaps = sol.latencies(
        due_in, [never], loop.lives, t_open, t_close, worst=31.0)
    assert attempted == 3 and failed == 1
    # a: due 0.05, sent at 0.1 (the step in progress ended), token at 0.2
    assert a.sent == pytest.approx(0.1) and a.token_t[0] == pytest.approx(0.2)
    assert sorted(ttft) == pytest.approx([0.15, 0.4 - 0.32 + 0.1, 31.0])
    # gaps: 100 ms each; the pre-roll's gaps inside the window count too
    assert all(g == pytest.approx(0.1) for g in gaps)
    assert len(gaps) == 2 + 2 + len([t for t in b.token_t[1:] if t < t_close])
    # tokens stamped after the close are not in the window's count
    assert early.counted is False and a.handle.done
