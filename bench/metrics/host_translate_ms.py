"""Host seconds per launch around the device wait: the session's
``translate`` span plus its ``launch`` span's time outside the
``device_sync`` child (dispatch, the device-to-host copy and the slices
of the rows to their real shape). The permutation gather back to
original ids is not in it: it runs after ``launch`` closes, as the
``unpermute`` span."""


def read(ctx):
    total = {"translate": 0.0, "launch": 0.0, "device_sync": 0.0}
    launches = 0
    for s in ctx.spans:
        if s["name"] in total:
            total[s["name"]] += s["dur"]
            launches += s["name"] == "launch"
    if not launches:
        return None
    host_us = total["translate"] + total["launch"] - total["device_sync"]
    return 1e-3 * host_us / launches
