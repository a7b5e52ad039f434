"""The benchmark's copies of the traffic generators, pinned by checksum at
each cell's parameters, so a change to them cannot pass unseen."""

import hashlib
import json

import numpy as np
import pytest

from bench import generators, harness

SEED = 2**33 + 12345


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, np.int64).tobytes())
    return h.hexdigest()[:16]


def _traffic(name):
    return json.loads((harness.BENCH / "traffic" / f"{name}.json").read_text())


PINNED = {
    "decode.l1": "c8f62f2acc9fe300",
    "decode.sweep64": "744382db12837914",
    "serve.l8": "d92f28a6bb8e7800",
}


@pytest.mark.parametrize("name", [n for n in PINNED if "decode" in n])
def test_decode_trace_checksum(name):
    t = _traffic(name)
    arrays = generators.get(t["generator"])(t["params"], SEED)
    assert _digest(arrays) == PINNED[name]


def test_decode_seeds_reorder_the_same_requests():
    t = _traffic("decode.l1")
    a = generators.get(t["generator"])(t["params"], 1)
    b = generators.get(t["generator"])(t["params"], 2)
    assert a[0].shape == (96 * 17,)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[2], b[2])
    assert not np.array_equal(a[1], b[1])
    # the same addresses inside every token's burst, in another order
    assert np.array_equal(np.sort(a[1].reshape(96, 17), axis=1),
                          np.sort(b[1].reshape(96, 17), axis=1))


def test_serving_seeds_reorder_the_same_requests():
    t = _traffic("serve.l8")
    gen = generators.get(t["generator"])
    (la, sa), (lb, sb) = gen(t["params"], 1), gen(t["params"], 2)
    assert sa != sb
    for ra, rb in zip(la, lb):
        assert [r.arrival for r in ra] == [r.arrival for r in rb]
        assert sorted((r.prompt_tokens, r.decode_tokens) for r in ra) == \
            sorted((r.prompt_tokens, r.decode_tokens) for r in rb)


def test_serving_requests_checksum():
    t = _traffic("serve.l8")
    lists, seeds = generators.get(t["generator"])(t["params"], SEED)
    assert len(lists) == len(seeds) == 8
    rows = [(lane, r.rid, r.arrival, r.prompt_tokens, r.decode_tokens)
            for lane, reqs in enumerate(lists) for r in reqs]
    assert _digest([np.asarray(rows).ravel(), np.asarray(seeds)]) == \
        PINNED["serve.l8"]


def test_job_seeds_differ_and_take_large_seeds():
    seeds = {generators.job_seed(2**40 + 3, k) for k in range(50)}
    assert len(seeds) == 50
    assert generators.job_seed(5, 1) == generators.job_seed(5, 1)



def test_decode_draw_seeds_draw_other_requests():
    t = _traffic("decode.l1")
    gen = generators.get(t["generator"])
    a = gen(dict(t["params"], draw_seed=1), 5)
    b = gen(dict(t["params"], draw_seed=2), 5)
    # the same shapes and arrival times, another set of KV addresses
    assert np.array_equal(a[0], b[0]) and a[1].shape == b[1].shape
    assert not np.array_equal(np.sort(a[1]), np.sort(b[1]))


def test_serving_draw_seeds_draw_other_requests():
    t = _traffic("serve.l8")
    gen = generators.get(t["generator"])
    la, _ = gen(dict(t["params"], draw_seed=1), 5)
    lb, _ = gen(dict(t["params"], draw_seed=2), 5)
    assert len(la) == len(lb) == 8
    assert [[r.arrival for r in x] for x in la] != \
        [[r.arrival for r in x] for x in lb]
