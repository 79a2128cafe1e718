"""The traffic generator: a seed fixes the requests; every seed gets the
same multiset of gaps and lengths."""
import numpy as np
import pytest

from bench import spec, traffic

SEED = 2**33 + 17          # wider than 32 bits, as the driver's are


@pytest.mark.parametrize("mix_name", ["chat-poisson", "docqa-backlog"])
def test_same_seed_same_requests(mix_name):
    mix = spec.load_json(spec.BENCH / "traffic" / f"{mix_name}.json")
    a = traffic.plan(mix, SEED, 51.0, 126_464, 32)
    b = traffic.plan(mix, SEED, 51.0, 126_464, 32)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.n_blocks, x.due_s) == (y.n_blocks, y.due_s)


@pytest.mark.parametrize("mix_name", ["chat-poisson", "docqa-backlog"])
def test_other_seed_same_work_other_order(mix_name):
    mix = spec.load_json(spec.BENCH / "traffic" / f"{mix_name}.json")
    a = traffic.plan(mix, SEED, 51.0, 126_464, 32)
    b = traffic.plan(mix, SEED + 1, 51.0, 126_464, 32)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.n_blocks for p in a) == sorted(p.n_blocks for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert not np.array_equal(a[0].prompt[:4], b[0].prompt[:4])


def test_open_loop_dues_fill_the_window():
    """The lead-in and the window each get their own multiset of gaps and
    lengths: two seeds offer the window the same work in another order."""
    mix = spec.load_json(spec.BENCH / "traffic" / "chat-poisson.json")
    lead, rate = mix["lead_in_s"], mix["arrival"]["rate_per_s"]
    plan = traffic.plan(mix, SEED, 51.0, 1000, 32)
    other = traffic.plan(mix, SEED + 5, 51.0, 1000, 32)
    due = np.array([p.due_s for p in plan])
    assert len(plan) == int(rate * lead) + int(rate * 51.0)
    assert due[0] == -lead and np.all(np.diff(due) > 0) and due[-1] < 51.0
    assert (due >= 0).sum() == int(rate * 51.0) and 0.0 in due

    def window(pl):
        inside = [p for p in pl if p.due_s >= 0]
        d = np.array([p.due_s for p in inside])
        return (np.sort(np.diff(np.append(d, 51.0))),
                sorted(len(p.prompt) for p in inside),
                sorted(p.n_blocks for p in inside))
    (ga, pa, ba), (gb, pb, bb) = window(plan), window(other)
    assert np.allclose(ga, gb) and pa == pb and ba == bb
    assert [len(p.prompt) for p in plan] != [len(p.prompt) for p in other]


def test_output_tokens_in_whole_blocks():
    """The chat mix states outputs in tokens (the trace's unit); the server
    is asked for whole blocks, at most its --gen-length."""
    mix = spec.load_json(spec.BENCH / "traffic" / "chat-poisson.json")
    n = 1000
    toks = traffic.lengths(mix["output_tokens"], n)
    blocks = traffic.output_blocks(mix, n, 32)
    assert np.array_equal(blocks, -(-toks // 32))
    assert blocks.min() == 1 and blocks.max() == 256 // 32
    assert int(np.median(toks)) == mix["output_tokens"]["median"]


def test_length_laws():
    ln = traffic.lengths({"dist": "lognormal", "median": 128, "sigma": 0.9,
                          "min": 16, "max": 512}, 1001)
    assert ln.min() >= 16 and ln.max() <= 512 and int(np.median(ln)) == 128
    lu = traffic.lengths({"dist": "loguniform", "min": 512, "max": 2048}, 100)
    assert lu.min() >= 512 and lu.max() <= 2048
    assert abs(np.exp(np.mean(np.log(lu))) - 1024) < 30
    ch = traffic.lengths({"dist": "choice", "values": [1, 2, 3, 4],
                          "weights": [0.5, 0.3, 0.15, 0.05]}, 40)
    assert list(np.bincount(ch)[1:]) == [20, 12, 6, 2]
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf"}, 3)
