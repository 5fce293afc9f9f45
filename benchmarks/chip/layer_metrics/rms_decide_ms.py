"""rms_decide_ms: the RMS's own host-clock decision time
(``Decision.schedule_time_s`` through ``DMR.history``), averaged over all
of the window's reconfiguration checks.  Layer: the RMS
(``rms/policy.py`` via ``runtime/local_rms.py`` and ``core/dmr.py``).
Moves ``reconfig_s``.
"""


def read(ctx):
    times = [h.schedule_time_s for h in ctx.dmr_history]
    if not times:
        return None
    return 1e3 * sum(times) / len(times), "ms"
