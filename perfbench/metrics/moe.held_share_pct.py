"""Of the token-expert pairs the routers chose in the window, the share that
landed on experts this chip holds (counted on the device, every expert layer,
admissions and decode steps alike)."""


def read(ctx):
    routed = ctx["counters"].get("engine.moe_assignments")
    held = ctx["counters"].get("engine.moe_assignments_held")
    if not routed or held is None:
        return None
    return 100.0 * held / routed
