"""serve_rows_per_s: every row scored in the window over the window's time
(which ends with the last batch that started in it)."""


def read(run):
    rows = sum(u.get("rows", 0) for u in run.units)
    if not rows:
        return None
    return rows / run.window_s
