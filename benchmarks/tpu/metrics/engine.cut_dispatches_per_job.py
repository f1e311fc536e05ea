"""Round-program dispatches of the traced job that hold part of a split
bucket: the ``engine.cut_dispatches`` counter summed over splits and
sizes. The engine cuts a bucket where the compiler refused it whole for
want of device memory; ``None`` where nothing was cut or the program
keeps no such counter."""


def read(ctx):
    tel = ctx.telemetry
    cuts = (tel.counters_by_name("engine.cut_dispatches")
            if tel is not None else {})
    return float(sum(cuts.values())) if cuts else None
