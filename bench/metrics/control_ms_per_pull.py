"""Host time of the controller pull (counter harvest, policy, migration
plan and its dispatch, table graft), per pull: the ``control`` stage
timer over the traced run's window, divided by the pulls it timed."""


def read(ctx):
    st = ctx["stages"].get("control")
    if st is None or not st["calls"]:
        return None
    return st["s"] / st["calls"] * 1e3
