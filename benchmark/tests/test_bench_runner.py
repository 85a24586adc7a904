"""The training runner's own pieces, without a device."""

import time

import numpy as np

from benchmark.harness import manifest as mf

runner = mf.load_runner("train")


class Loss:
    """Stands for a device array that is ready ``delay`` after dispatch."""

    def __init__(self, log, i, delay):
        self.log, self.i, self.ready_at = log, i, time.perf_counter() + delay

    def block_until_ready(self):
        self.log.append(("wait", self.i))
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))


def test_drive_keeps_one_step_in_flight_and_stamps_every_step():
    log = []

    def step(params, opt_state, tokens, targets):
        log.append(("dispatch", params))
        return params + 1, opt_state, Loss(log, params, 0.01)

    data = iter(lambda: (None, None), 1)
    spans = runner.Spans()
    state, start, stamps, losses, error = runner.drive(
        step, (0, None), data, lambda n, s: n >= 4, spans)
    assert error is None and state == (4, None)
    # step i+1 is dispatched BEFORE step i is waited for
    assert log == [("dispatch", 0), ("dispatch", 1), ("wait", 0),
                   ("dispatch", 2), ("wait", 1), ("dispatch", 3),
                   ("wait", 2), ("wait", 3)]
    assert len(stamps) == len(losses) == 4 and stamps[0] > start
    assert all(b > a for a, b in zip(stamps, stamps[1:]))
    assert [len(spans.seconds[k]) for k in runner.SPAN_NAMES] == [4, 4, 4]


def test_drive_reports_a_failed_step():
    def step(params, opt_state, tokens, targets):
        if params == 2:
            raise RuntimeError("out of memory")
        return params + 1, opt_state, Loss([], params, 0.0)

    _, _, stamps, losses, error = runner.drive(
        step, (0, None), iter(lambda: (None, None), 1),
        lambda n, s: n >= 10, runner.Spans())
    assert isinstance(error, RuntimeError) and len(losses) == 2


def test_check_batch_is_a_function_of_the_seed_and_tiles():
    seed = 2 ** 31 + 12345  # more than 32 signed bits hold
    (x, y), (xs, ys) = runner.check_batch(50257, 4, 32, 1024, seed)
    again = runner.check_batch(50257, 4, 32, 1024, seed)
    other = runner.check_batch(50257, 4, 32, 1024, seed + 1)
    assert x.shape == (4, 1024) and xs.shape == (32, 1024)
    assert np.array_equal(x[:, 1:], y[:, :-1])      # next-token targets
    assert np.array_equal(xs, np.tile(x, (8, 1)))
    assert np.array_equal(again[1][0], xs)
    assert not np.array_equal(other[1][0], xs)
    assert 0 <= x.min() and x.max() < 50257


def test_table_idle_is_the_exact_count():
    assert runner.table_idle("1F1B", 4, 8) == (24, 88)   # 27.27%
