"""The trace reduction on small synthetic traces."""
from bench import trace_reduce as tr


def _trace(ops, mods=(), spans=(), n_devices=1):
    return tr.Trace(list(ops), list(mods), list(spans), n_devices)


def test_busy_is_the_union_of_overlapping_ops():
    t = _trace([(0, "a", 10, 30), (0, "b", 20, 40), (0, "c", 50, 60)],
               spans=[(tr.WINDOW_SPAN, 0, 100)])
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 100)
    assert tr.busy_ns(t, lo, hi) == 40        # [10, 40) and [50, 60)


def test_busy_clips_to_the_window_and_averages_devices():
    t = _trace([(0, "a", 0, 50), (1, "a", 40, 80)], n_devices=2)
    # device 0 busy [10, 50) = 40, device 1 busy [40, 60) = 20
    assert tr.busy_ns(t, 10, 60) == 30


def test_no_device_reads_zero_busy():
    assert tr.busy_ns(_trace([], n_devices=0), 0, 100) == 0


def test_program_time_sums_executions_by_name():
    mods = [(0, "jit_epoch_loop", 0, 10), (0, "jit_epoch_loop", 20, 25),
            (0, "jit_other", 30, 40)]
    t = _trace([], mods)
    assert tr.program_ns(t, 0, 100, contains="epoch_loop") == {
        "jit_epoch_loop": 15}
    assert tr.program_ns(t, 22, 100) == {"jit_epoch_loop": 3,
                                         "jit_other": 10}


def test_top_ops_orders_by_time():
    t = _trace([(0, "x", 0, 5), (0, "y", 5, 20), (0, "x", 20, 21)])
    assert tr.top_ops(t, 0, 100) == [["y", 15e-9], ["x", 6e-9]]


def test_idle_gaps_take_the_span_innermost_for_most_of_them():
    ops = [(0, "op", 0, 10), (0, "op", 40, 50), (0, "op", 55, 100)]
    spans = [(tr.WINDOW_SPAN, 0, 100), ("online.begin_epoch", 5, 45),
             ("epoch_cache.fingerprint", 15, 35)]
    t = _trace(ops, spans=spans)
    # [10, 40): the fingerprint is innermost for 20 ns, begin for 10
    assert tr.idle_gaps(t, 0, 100) == [["epoch_cache.fingerprint", 30e-9],
                                       ["harness", 5e-9]]
    spans[2] = ("epoch_cache.fingerprint", 15, 20)
    # now begin_epoch is innermost for 25 of the 30 ns
    t = _trace(ops, spans=spans)
    assert tr.idle_gaps(t, 0, 100)[0] == ["online.begin_epoch", 30e-9]


def test_union_merges_touching_intervals():
    assert tr.union([(0, 5), (5, 8), (10, 12)], 0, 11) == [[0, 8], [10, 11]]
