"""Output tokens generated inside the window over the window's length."""
NAME, UNIT = "output_tok_s", "tokens/s"


def read(run):
    n = sum(1 for p in run.planned
            for t in (p.token_times)
            if run.in_window(t))
    return n / run.window_s if n else None
