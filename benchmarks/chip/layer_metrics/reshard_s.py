"""reshard_s: the program's own host-clock time of each resize
(``ResizeHandler.resize_time_s``: ``reshard`` of the whole state, ended by
``block_until_ready``), averaged over the window's resizes.  Layer: the
reshard (``core/reshard.py`` via ``ElasticTrainer.maybe_reconfigure``).
Moves ``reconfig_s``.
"""


def read(ctx):
    times = [h.resize_time_s for h in ctx.dmr_history
             if h.action.name in ("EXPAND", "SHRINK")]
    if not times:
        return None
    return sum(times) / len(times), "s"
