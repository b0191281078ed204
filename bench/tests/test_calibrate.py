"""The numbers a sweep sets: deadlines from the low-load run, the rate at
four fifths of the highest sustained rate (90% of pipelines in time, none
unfinished, no growing queue)."""
from bench.calibrate import choose, sustained


def run(rate, finished, n, q0, q1, attain=100.0):
    return {"rate": rate, "counts": {"pipelines": n, "pipelines_finished": finished,
                                     "waiting_at_start": q0, "waiting_at_end": q1,
                                     "slo_attain_pct": attain}}


MIX = {"rate_per_s": 9.0, "classes": {"vrag": {"weight": 3, "deadline_s": 1.0},
                                      "srag": {"weight": 1, "deadline_s": 2.0}}}
LOW = {"per_class": {"vrag": {"mean_e2e_s": 6.04}, "srag": {"mean_e2e_s": None}}}


def test_rate_is_four_fifths_of_the_highest_sustained_rate():
    swept = [run(0.35, 10, 10, 0, 0), run(0.5, 15, 15, 0, 2), run(0.65, 19, 20, 0, 1),
             run(0.8, 24, 24, 0, 9), run(0.6, 18, 18, 0, 0, attain=88.9),
             run(0.55, 16, 16, 0, 0, attain=None)]
    assert [sustained(s) for s in swept] == [True, True, False, False, False, False]
    mix = choose(MIX, LOW, swept)
    assert mix["rate_per_s"] == 0.4
    assert mix["classes"]["vrag"]["deadline_s"] == 12.1
    assert mix["classes"]["srag"]["deadline_s"] == 2.0      # not finished at low load
    assert MIX["rate_per_s"] == 9.0                         # the input is left alone


def test_nothing_sustained_takes_the_lowest_rate():
    mix = choose(MIX, LOW, [run(0.5, 3, 15, 0, 6), run(0.8, 2, 24, 1, 9)])
    assert mix["rate_per_s"] == 0.4
