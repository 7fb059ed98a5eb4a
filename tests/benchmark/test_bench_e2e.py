"""End-to-end metric arithmetic: tails over every request, timed from
the due time; tokens/s over the whole window; a stall moves them all."""
import dataclasses
from typing import List

from benchmarks.chip import e2e


@dataclasses.dataclass
class R:
    due: float
    token_times: List[float]
    in_window: bool = True


@dataclasses.dataclass
class Rec:
    requests: list
    window_start: float = 0.0
    window_s: float = 10.0
    drain_end: float = 20.0
    process_start: float = -5.0


def _steady(n=100, stall_at=None, stall=1.0):
    """n requests, one every 0.1 s, each: first token 50 ms after it is
    due, then 9 tokens 20 ms apart; a stall delays everything after
    ``stall_at`` seconds by ``stall``."""
    reqs = []
    for i in range(n):
        due = 0.1 * i
        times = [due + 0.05 + 0.02 * k for k in range(10)]
        if stall_at is not None:
            times = [t + stall if t >= stall_at else t for t in times]
        reqs.append(R(due=due, token_times=times))
    return Rec(requests=reqs)


def test_nearest_rank():
    assert e2e.nearest_rank(range(1, 101), 90) == 90
    assert e2e.nearest_rank([5.0], 95) == 5.0
    assert e2e.nearest_rank([], 50) is None


def test_ttft_is_timed_from_the_due_time_over_all_requests():
    rec = _steady()
    assert abs(e2e.ttft_p90_ms(rec) - 50.0) < 1e-6
    # a request sent late (or queued) still counts from its due time
    rec.requests[0].token_times = [t + 2.0 for t in
                                   rec.requests[0].token_times]
    assert sorted(e2e.ttfts(rec.requests, 20.0))[-1] > 2.0
    # requests outside the window do not count; one with no first token
    # counts its whole wait, to the end of the drain
    rec.requests[0].in_window = False
    rec.requests[1].token_times = []
    assert max(e2e.ttfts(rec.requests, 20.0)) == 20.0 - 0.1


def test_itl_takes_every_gap():
    rec = _steady()
    assert len(e2e.token_gaps(rec.requests)) == 100 * 9
    assert abs(e2e.itl_p95_ms(rec) - 20.0) < 1e-6


def test_tokens_per_s_is_over_the_whole_window():
    rec = _steady()
    inside = sum(1 for r in rec.requests for t in r.token_times if t < 10)
    assert e2e.output_tokens_per_s(rec) == inside / 10.0
    rec.window_s = 5.0
    inside = sum(1 for r in rec.requests for t in r.token_times if t < 5)
    assert e2e.output_tokens_per_s(rec) == inside / 5.0


def test_setup_runs_from_process_start_to_the_window():
    assert e2e.setup_s(_steady()) == 5.0


def _busy(stall_at=None):
    """40 requests due 0.1 s apart, each 10 tokens 0.5 s apart (about 20
    in flight); a stall freezes the engine for 1 s at ``stall_at``, so
    every event after it comes 1 s later."""
    reqs = []
    for i in range(40):
        due = 0.1 * i
        times = [due + 0.05 + 0.5 * k for k in range(10)]
        if stall_at is not None:
            times = [t + 1.0 if t >= stall_at else t for t in times]
        reqs.append(R(due=due, token_times=times))
    return Rec(requests=reqs, window_s=6.0)


def test_a_one_second_stall_moves_every_metric():
    base, stalled = _busy(), _busy(stall_at=2.0)
    assert e2e.ttft_p90_ms(stalled) - e2e.ttft_p90_ms(base) > 900
    assert e2e.itl_p95_ms(stalled) - e2e.itl_p95_ms(base) > 900
    assert e2e.output_tokens_per_s(stalled) < \
        e2e.output_tokens_per_s(base) - 5
