"""Share of the window's step-program calls that updated the paged KV
pool in place, in percent: the program's
``serve_pool_inplace_total{outcome="kept"}`` over the window, divided by
``kept`` plus ``copied``, summed over the pods. A call is ``kept`` when
the pool arrays it was given were consumed (the donation was used), and
``copied`` when they survived it (the step wrote a whole new pool). A
program without the counter reads nothing."""
LAYER = "model step (models/model.py, serve/fused.py)"
SOURCE = "program_counter"
NAME = "serve_pool_inplace_total"


def outcome_total(snap: dict, outcome: str) -> float:
    return sum(float(s["value"]) for s in snap["metrics"]
               if s["name"] == NAME
               and s["labels"].get("outcome") == outcome)


def read(ctx):
    rec = ctx["rec"]
    kept, copied = (outcome_total(rec["metrics1"], o)
                    - outcome_total(rec["metrics0"], o)
                    for o in ("kept", "copied"))
    calls = kept + copied
    return 100.0 * kept / calls if calls > 0 else None
