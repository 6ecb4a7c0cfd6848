"""The window's job loop, its rate arithmetic and the output check."""

import numpy as np
import pytest

from bench import check, window


class FakeClock:
    """Each job takes ``job_s`` of fake time."""

    def __init__(self, job_s):
        self.t, self.job_s = 100.0, job_s

    def __call__(self):
        return self.t

    def job(self, keys):
        self.t += self.job_s
        return np.sort(keys), {}


def _sets(n=8, k=3):
    return [np.arange(n, 0, -1, dtype=np.int32) + i for i in range(k)]


@pytest.mark.parametrize("job_s,seconds,want_jobs", [
    (10.0, 40.0, 4),     # ends exactly at 40 s: that job closes it
    (15.0, 40.0, 3),     # the first job to end after 40 s closes it
    (50.0, 40.0, 1),     # a job longer than the window still counts whole
    (1.0, 0.0, 1),       # a traced run: one job
])
def test_window_holds_whole_jobs_until_seconds_have_passed(job_s, seconds,
                                                           want_jobs):
    clk = FakeClock(job_s)
    jobs, outs = window.run_window(clk.job, _sets(), seconds, clock=clk)
    assert len(jobs) == len(outs) == want_jobs
    assert [j.key_set for j in jobs] == [i % 3 for i in range(want_jobs)]
    assert window.rate(jobs) == pytest.approx(8 / job_s)


def test_rate_is_all_keys_over_first_start_to_last_end():
    jobs = [window.Job(n=1000, t0=0.0, t1=2.0, key_set=0),
            window.Job(n=1000, t0=2.5, t1=4.0, key_set=1),
            window.Job(n=2000, t0=4.0, t1=5.0, key_set=2)]
    # 4000 keys over 5 s, the gap between jobs included.
    assert window.rate(jobs) == pytest.approx(800.0)
    jobs[1].ok = False            # a failed job sorted nothing
    assert window.rate(jobs) == pytest.approx(600.0)


def test_max_jobs_stops_the_window():
    clk = FakeClock(1.0)
    jobs, _ = window.run_window(clk.job, _sets(), 1e9, max_jobs=2, clock=clk)
    assert len(jobs) == 2


def test_a_job_that_raises_is_counted_as_failed():
    clk = FakeClock(5.0)

    def flaky(keys):
        clk.t += 5.0
        if keys[0] == 9:          # key set 1
            raise OverflowError("capacity")
        return np.sort(keys), {}

    jobs, outs = window.run_window(flaky, _sets(), 12.0, clock=clk)
    assert [j.ok for j in jobs] == [True, False, True]
    assert outs[1] is None
    sets = _sets()
    v = check.judge(outs, [j.key_set for j in jobs], sets, np.sort)
    assert not v["correct"] and v["failed"] == 1
    assert v["numbers"]["mismatched_keys"]["value"] == 8


@pytest.mark.parametrize("out,want", [
    ([1, 2, 3, 4], 0),
    ([1, 2, 4, 3], 2),
    ([1, 2, 3], 1),           # a key missing
    ([1, 2, 3, 4, 4], 1),     # a key extra
    ([], 4),
])
def test_mismatched_counts_positions_and_length(out, want):
    assert check.mismatched(np.array(out, np.int32),
                            np.array([1, 2, 3, 4], np.int32)) == want


def test_judge_passes_exact_outputs_with_limit_zero():
    sets = _sets()
    outs = [np.sort(s) for s in sets]
    v = check.judge(outs, [0, 1, 2], sets, np.sort)
    assert v["correct"] and v["failed"] == 0
    assert v["numbers"]["mismatched_keys"] == {"value": 0, "limit": 0}
    assert v["numbers"]["jobs_checked"]["value"] == 3
