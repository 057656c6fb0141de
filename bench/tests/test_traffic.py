"""One seed gives one trace; another seed the same work in another order."""
import numpy as np

from bench import harness, traffic

CHAT = harness.load_json(harness.BENCH / "traffic" / "chat.json")


def _key(reqs):
    return [(r.rid, r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


def test_request_trace_is_a_function_of_the_seed():
    a = traffic.request_trace(CHAT, 2**33 + 7, 20.0, 50304)
    b = traffic.request_trace(CHAT, 2**33 + 7, 20.0, 50304)
    assert _key(a) == _key(b)


def test_other_seeds_get_the_same_work_with_other_tokens():
    a = traffic.request_trace(CHAT, 1, 51.0, 50304)
    b = traffic.request_trace(CHAT, 2, 51.0, 50304)
    ramp = [r for r in a if r.due_s < 0]
    assert len(ramp) == round(CHAT["rate"] * CHAT["ramp_s"])
    assert len(a) - len(ramp) == round(CHAT["rate"] * 51.0)
    assert [(r.rid, r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.rid, r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert all(x.prompt.tobytes() != y.prompt.tobytes() for x, y in zip(a, b))
    due = [r.due_s for r in a]
    assert due == sorted(due) and -CHAT["ramp_s"] < due[0] and due[-1] < 51.0
    assert all(r.rid.startswith("w") == (r.due_s < 0) for r in a)


def test_lengths_stay_inside_their_clips():
    reqs = traffic.request_trace(CHAT, 3, 50.0, 50304)
    p, o = CHAT["prompt_len"], CHAT["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(r.prompt.min() >= 0 and r.prompt.max() < 50304 for r in reqs)


def test_markov_batch_is_a_function_of_seed_and_step():
    tab = traffic.markov_table(1024, 8, 5)
    t1, l1 = traffic.markov_batch(tab, 5, 3, 4, 64)
    t2, l2 = traffic.markov_batch(traffic.markov_table(1024, 8, 5), 5, 3, 4, 64)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(l1, l2)
    # labels are the next tokens, and every row follows the table
    np.testing.assert_array_equal(t1[:, 1:], l1[:, :-1])
    for row_t, row_l in zip(t1, l1):
        assert all(b in tab[a] for a, b in zip(row_t, row_l))
    assert len({r.tobytes() for r in t1}) == 4  # rows all differ
    t3, _ = traffic.markov_batch(tab, 5, 4, 4, 64)
    assert not np.array_equal(t1, t3)
