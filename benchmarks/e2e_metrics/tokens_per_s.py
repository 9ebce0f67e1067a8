"""Prompt tokens whose first token arrived, plus output tokens that
arrived, inside the window, per second of window."""
from benchmarks import stats


def compute(run):
    prompt, out = stats.tokens_in_window(run["timelines"], run["t_open"],
                                         run["t_close"])
    return (prompt + out) / (run["t_close"] - run["t_open"])
