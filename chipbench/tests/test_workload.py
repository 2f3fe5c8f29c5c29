"""The traffic generator: seeded, bounded, the same work for every seed."""

import collections

import pytest

from chipbench import workload

VOCAB = 50304
SEED = 2 ** 31 + 5

#: a shared-system-prompt chat mix, as a later cell's traffic file would hold
CHAT = {"arrival": {"kind": "poisson", "rate_per_s": 2.5},
        "shared_prefixes": {"count": 4, "length": 512,
                            "popularity": [0.55, 0.25, 0.12, 0.08]},
        "prompt_len": {"dist": "loguniform", "lo": 32, "hi": 256},
        "output_len": {"dist": "loguniform", "lo": 8, "hi": 64},
        "max_total": 832, "drain": True}


def mix_of(name):
    return CHAT if name == "chat" else workload.load_mix(name)


@pytest.mark.parametrize("mix", ["decode", "chat"])
def test_same_seed_same_requests(mix):
    m = mix_of(mix)
    a = workload.generate(m, SEED, 40.0, VOCAB)
    b = workload.generate(m, SEED, 40.0, VOCAB)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]
    c = workload.generate(m, SEED + 1, 40.0, VOCAB)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("mix", ["decode", "chat"])
def test_lengths_respect_the_context(mix):
    m = mix_of(mix)
    for seed in (0, 7, 2 ** 31 + 3, 2 ** 33 + 1):
        for r in workload.generate(m, seed, 40.0, VOCAB):
            assert 1 <= r.max_new_tokens
            assert len(r.prompt) + r.max_new_tokens <= m["max_total"]
            assert all(1 <= t < VOCAB for t in r.prompt)


@pytest.mark.parametrize("mix", ["decode", "chat"])
def test_every_seed_gets_the_same_work(mix):
    """Another seed reorders the same lengths and gaps."""
    m = mix_of(mix)
    a = workload.generate(m, 1, 40.0, VOCAB)
    b = workload.generate(m, 2, 40.0, VOCAB)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    if m["max_total"] >= m["prompt_len"]["hi"] + m["output_len"]["hi"] \
            + m.get("shared_prefixes", {}).get("length", 0):
        assert sorted(r.max_new_tokens for r in a) == \
            sorted(r.max_new_tokens for r in b)
    gaps = lambda rs: sorted(round(y.due_s - x.due_s, 9)  # noqa: E731
                             for x, y in zip(rs, rs[1:]))
    if m["arrival"]["kind"] == "poisson":
        assert len(a) == round(m["arrival"]["rate_per_s"] * 40.0)
        # n gaps are drawn; the first request is due at 0, so n - 1 are used
        common = collections.Counter(gaps(a)) & collections.Counter(gaps(b))
        assert sum(common.values()) >= len(a) - 2
        assert max(r.due_s for r in a) < 40.0


def test_a_backlog_window_gets_the_same_work_from_every_seed():
    """The first requests, all that a short window serves, are a fair
    sample of the lengths under every seed."""
    m = workload.load_mix("decode")
    full = workload.generate(m, 1, 40.0, VOCAB)
    mean = sum(r.max_new_tokens for r in full) / len(full)
    for seed in (2, 2 ** 31 + 9, 2 ** 33 + 5):
        head = workload.generate(m, seed, 40.0, VOCAB)[:workload.STRATUM]
        got = sum(r.max_new_tokens for r in head) / len(head)
        assert got == pytest.approx(mean, rel=0.04)


def test_prefix_popularity_and_sharing():
    m = CHAT
    reqs = workload.generate(m, SEED, 40.0, VOCAB)
    heads = collections.Counter(tuple(r.prompt[:512]) for r in reqs)
    counts = sorted(heads.values(), reverse=True)
    n = len(reqs)
    assert len(heads) == 4
    assert counts[0] == pytest.approx(0.55 * n, abs=1)
    assert all(r.prefix_len == 512 for r in reqs)


def test_shadow_warms_each_class_without_touching_the_window():
    m = CHAT
    reqs = workload.generate(m, SEED, 40.0, VOCAB)
    waves = workload.shadow(reqs, VOCAB, 16)
    assert len(waves) == 3
    window = {tuple(r.prompt[:16]) for r in reqs}
    for w in waves:
        for r in w:
            assert tuple(r.prompt[:16]) not in window
            assert r.max_new_tokens == 2
    # wave 3 shares exactly the prefix of its wave-2 lead, nothing more
    leads = {tuple(r.prompt[:512]): r.prompt for r in waves[1]}
    for r in waves[2]:
        lead = leads[tuple(r.prompt[:512])]
        assert r.prompt[512:528] != lead[512:528]
    suffix = {(len(r.prompt) - 512 - 1).bit_length() for r in waves[2]}
    assert suffix == {(len(r.prompt) - 512 - 1).bit_length() for r in reqs}


def test_the_step_under_way_at_the_end_counts_whole():
    """The window closes when the step under way at its end returns: that
    step's tokens count, over the time up to its return."""
    from chipbench import driver, run
    req = workload.Req(0.0, [1, 2, 3], 4)
    # two requests; steps return at 1, 2 and 3.5 s; the time is up at 3 s
    served = [driver.Served(req, 0.0, stamps=[1.0, 2.0, 3.5]),
              driver.Served(req, 0.0, stamps=[2.0, 3.5])]
    d = driver.Drive(0.0, 3.0, served, [], 0, 0, close=3.5)
    e2e = run.end_to_end(d)
    assert e2e["tokens_per_s"] == pytest.approx(5 / 3.5)
    # gaps 1.0, 1.5 and 1.5 s, the last two ending at the close
    assert e2e["itl_p95_ms"] == pytest.approx(1500.0)
