"""Share of the launches' lane steps that did work: the loop steps the
real lanes needed (``engine_lane_steps_total``) over the steps each
launch ran times its padded lane count (``engine_lane_slots_total``).
Lanes that converge early, and pad lanes, lower it."""


def read(ctx):
    slots = ctx.counters.get("engine_lane_slots_total")
    if not slots:
        return None
    return 100.0 * ctx.counters.get("engine_lane_steps_total", 0) / slots
