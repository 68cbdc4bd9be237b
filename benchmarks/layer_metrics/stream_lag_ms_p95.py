"""front door (serving/server.py `_generate`): the 95th percentile of all
`lag_s` of the window's `stream` events, one lag a streamed token: the
token's line flushed to the socket, on the engine's clock, less the
engine's stamp of the step that made it (the stream queue, the handler
thread's wake-up, the JSON line, the flush), in milliseconds. Read over the
part of the window before the profiler's trace was stopped
(`host_loop.quiet_window`)."""
from harness import host_loop, traffic


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    streams = spans.of_kind("stream", *host_loop.quiet_window(facts))
    lags = [1e3 * float(x) for _k, _t, f in streams
            for x in f.get("lag_s", ())]
    return traffic.percentile(lags, 95) if lags else None
