"""Median over the traced engine steps of the device's idle time inside the
step that does NOT lie inside an `engine.drain` span: the idle the host
causes (planning, building, launching and committing while nothing runs),
as against the idle while the host itself waits for the device. Device
intervals from the trace, spans moved onto its clock by the anchors
(program_spans). Also prints the traced run's consistency line."""
import program_spans


def read(ctx):
    program_spans.report(ctx, "engine.step")
    idle = program_spans.of(ctx).device_idle()
    if not idle or not idle[0]:
        return None
    return ctx["median"](outside / 1e6 for _, outside in idle[0])
