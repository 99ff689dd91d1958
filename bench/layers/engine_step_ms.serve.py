"""engine_step_ms.serve: mean time of one engine step, in ms.

The program's ``engine_step`` host spans (repro.obs, ServingEngine with
telemetry) over the traced window: admit, pack, transfer, serve_block,
fetch.  Moves ``serve_p50_ms``.
"""


def read(ctx):
    tel = getattr(ctx.driver, "tel", None)
    if tel is None:
        return None
    d = [s.duration for s in tel.spans if s.name == "engine_step"]
    return 1e3 * sum(d) / len(d) if d else None
