"""Share of slot-steps that carried a request across the window, from
the engine's own counters (``busy_slot_steps`` over ``steps`` times
``max_slots``)."""
NAME, UNIT = "slot_occupancy", "%"
LAYER, MOVES = "batching", "output_tok_s"


def read(run):
    a, b = run.counters.get("open"), run.counters.get("close")
    if not a or not b or b[0] <= a[0]:
        return None
    return 100.0 * (b[1] - a[1]) / ((b[0] - a[0]) * run.counters["max_slots"])
