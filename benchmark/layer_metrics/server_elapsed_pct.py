"""Layer: protocol front. The server's own clock per statement
(`QueryResults.stats.elapsedTimeMillis`, sent to every client) as a share of
the client's latency, summed over the window's statements. The rest is HTTP,
paging, row encoding on the server and decoding in the client."""


def read(run):
    timed = [r for r in run.completed if r.server_ms is not None]
    if not timed:
        return None
    return 100.0 * sum(r.server_ms for r in timed) / 1e3 / sum(r.latency for r in timed)
