"""ENU compaction's fill (%): frontier rows the ENU levels produced over
the capacity rows they were given (``level_sizes`` against the caps), over
the accepted chunks of the window."""


def read(ctx):
    cap = ctx.get("enu_capacity_rows")
    if not cap:
        return None
    return 100.0 * ctx["enu_rows"] / cap
