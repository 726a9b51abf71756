"""Host time per step of the Trainer's loop outside its step call: the
benchmark's clock around ``Trainer.run(1)`` minus the step call's own
``wall_s`` (data, transfer, straggler draw, timeout controller)."""


def read(rec):
    if not rec or rec.get("kind") != "train" or not rec["steps"]:
        return None
    steps = rec["steps"]
    return 1e3 * sum(s["s"] - s["call_s"] for s in steps) / len(steps)
