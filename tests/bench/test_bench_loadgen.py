"""Arrival schedules, the two loops, percentiles and the whole-batch
window, on a fake clock."""
import numpy as np
import pytest

from bench import graphs, stats, traffic


class FakeClock:
    """A clock that only moves when the server works or the loop sleeps."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def server(clock, seconds_per_batch, log):
    def serve(ids):
        log.append(list(ids))
        clock.t += seconds_per_batch
        return [f"answer-{k}" for k in ids]
    return serve


def test_poisson_due_same_gaps_for_every_seed():
    a = traffic.poisson_due(4.0, 30.0, seed=1)
    b = traffic.poisson_due(4.0, 30.0, seed=2**31 + 11)
    assert len(a) == len(b) == 120
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 30.0
    assert not np.allclose(a, b)
    # One multiset of gaps, in another order; they fill the window.
    ga = np.append(np.diff(a), 30.0 - a[-1])
    gb = np.append(np.diff(b), 30.0 - b[-1])
    assert np.sort(ga) == pytest.approx(np.sort(gb))
    assert ga.sum() == pytest.approx(30.0)
    assert np.mean(ga) == pytest.approx(0.25)
    np.testing.assert_array_equal(a, traffic.poisson_due(4.0, 30.0, seed=1))


def test_open_loop_schedule_is_the_mix_s_not_the_run_s():
    mix = {"loop": "open", "arrivals": "poisson", "rate_rps": 4.0,
           "order_seed": 5}
    a = traffic.due_times(mix, 30.0)
    np.testing.assert_array_equal(a, traffic.poisson_due(4.0, 30.0, seed=5))
    np.testing.assert_array_equal(a, traffic.due_times(dict(mix), 30.0))
    assert not np.allclose(
        a, traffic.due_times(dict(mix, order_seed=6), 30.0))
    with pytest.raises(ValueError):
        traffic.due_times(dict(mix, arrivals="bursty"), 30.0)


def test_open_loop_serves_due_requests_oldest_first_and_drains_backlog():
    clock = FakeClock()
    log = []
    due = np.array([0.0, 0.1, 0.2, 0.3, 0.35, 5.0])
    run = traffic.run_open(due, 2, server(clock, 1.0, log), clock=clock,
                           sleep=clock.sleep)
    # t=0: only request 0 is due; t=1: 1, 2 (cap 2); t=2: 3, 4; then the
    # loop sleeps until 5.0 for the last one.
    assert log == [[0], [1, 2], [3, 4], [5]]
    np.testing.assert_allclose(run.done, [1.0, 2.0, 2.0, 3.0, 3.0, 6.0])
    np.testing.assert_allclose(run.done - run.due,
                               [1.0, 1.9, 1.8, 2.7, 2.65, 1.0])
    assert run.answers[4] == "answer-4"
    assert len(run.lateness) == 1 and run.lateness[0] == pytest.approx(0.0)


def test_closed_loop_keeps_clients_outstanding_in_full_batches():
    clock = FakeClock()
    log = []
    run = traffic.run_closed(16, 8, 10.0, server(clock, 3.0, log),
                             clock=clock)
    assert log[0] == list(range(8)) and log[1] == list(range(8, 16))
    assert log[2] == list(range(16, 24))   # the first batch's clients again
    assert all(len(ids) == 8 for ids in log)
    assert len(log) == 4                   # no batch starts at or after 10 s
    assert len(run.done) == 32 and not np.isnan(run.done).any()
    rate, count, span = stats.whole_batch_rate(run.batches, 10.0)
    # Completions at 3, 6, 9, 12: the window is 3 .. 9, two whole batches.
    assert (count, span) == (16, pytest.approx(6.0))
    assert rate == pytest.approx(16 / 6.0)


def test_whole_batch_rate_needs_two_batches():
    b = [traffic.Batch(0.0, 5.0, [0, 1]), traffic.Batch(5.0, 11.0, [2, 3])]
    with pytest.raises(ValueError):
        stats.whole_batch_rate(b, 10.0)


@pytest.mark.parametrize("n, q, want", [(150, 90, 15), (100, 90, 10),
                                        (101, 90, 10), (40, 50, 20),
                                        (10, 95, 0)])
def test_samples_beyond_a_percentile(n, q, want):
    assert stats.beyond(n, q) == want


def test_percentile_and_spread():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 90) == pytest.approx(90.1)
    # Python's quartiles of 1..8 are 2.25 and 6.75 around a median of 4.5.
    assert stats.spread([1, 2, 3, 4, 5, 6, 7, 8]) == pytest.approx(1.0)


def test_upload_pool_is_seeded_and_distinct():
    base = np.zeros((5, 3), np.float32)
    rule = {"rule": "noise", "sigma": 0.1}
    a = traffic.upload_pool(base, 4, rule, seed=9)
    assert a.shape == (4, 5, 3) and a.dtype == np.float32
    np.testing.assert_array_equal(a, traffic.upload_pool(base, 4, rule, 9))
    assert not np.array_equal(a[0], a[1])
    assert 0.05 < float(a.std()) < 0.2


def test_redrawn_uploads_stay_one_hot():
    """SIoT uploads re-draw a share of the vertices' categories in every
    block and keep the rest as stored, so they stay one-hot."""
    raw = graphs.make("siot", 0.05, 0)
    rule = {"rule": "redraw", "share": 0.1}
    pool = traffic.upload_pool(raw.features, 4, rule, seed=2**31 + 1)
    np.testing.assert_array_equal(
        pool, traffic.upload_pool(raw.features, 4, rule, 2**31 + 1))
    blocks = graphs.onehot_blocks(raw.features.shape[1])
    for up in pool:
        assert set(np.unique(up)) == {0.0, 1.0}
        for first, width in blocks:
            np.testing.assert_array_equal(
                up[:, first:first + width].sum(axis=1), 1.0)
        moved = np.any(up != raw.features, axis=1).mean()
        assert 0.0 < moved <= 0.1
    assert not np.array_equal(pool[0], pool[1])
    with pytest.raises(ValueError, match="no upload rule"):
        traffic.upload_pool(raw.features, 1, {"rule": "x"}, 0)


@pytest.mark.parametrize("dataset, vertices, edges", [
    ("siot", 16216, 146117), ("yelp", 10000, 15683)])
def test_graphs_have_table_iii_counts(dataset, vertices, edges):
    """Table III's vertices and distinct undirected edges, no self loop."""
    raw = graphs.make(dataset, 1.0, 0)
    assert raw.num_vertices == vertices and len(raw.edges) == edges
    u, v = raw.edges[:, 0], raw.edges[:, 1]
    assert np.all(u != v)
    key = np.minimum(u, v) * vertices + np.maximum(u, v)
    assert len(np.unique(key)) == edges
