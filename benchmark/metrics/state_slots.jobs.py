"""Slot state: occupied slots whose state-space state one decode dispatch
advanced (label `state_slots` of the window's `lm_step` spans), mean. Every
slot of the grid is advanced by the program whatever it holds; these are the
ones whose state somebody reads."""


def read(run):
    from benchmark.harness.program_spans import program_spans
    vals = [d["lb"]["state_slots"]
            for d in program_spans(run, "lm_step") or ()
            if "state_slots" in d["lb"]]
    return sum(vals) / len(vals) if vals else None
