"""The most clients one round program holds where the compiler refused a
whole bucket: the least ``engine.fit_clients`` gauge of the traced job
(the engine sets it at each cut dispatch). ``None`` where nothing was
cut or the program keeps no such gauge."""

NAME = "engine.fit_clients"


def read(ctx):
    tel = ctx.telemetry
    fits = [v for k, v in (tel.gauges.items() if tel is not None else ())
            if k == NAME or k.startswith(NAME + "{")]
    return min(fits) if fits else None
