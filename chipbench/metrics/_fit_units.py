"""Shared by the fit readers: the window's units, untraced ones first."""


def units(ctx):
    all_units = ctx.record.get("units") or []
    plain = [u for u in all_units if not u.get("traced")]
    return plain or all_units


def traced_unit(ctx):
    return next((u for u in ctx.record.get("units") or [] if u.get("traced")), None)


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None
