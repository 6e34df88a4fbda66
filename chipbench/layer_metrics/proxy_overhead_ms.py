"""What the proxy, the router and the replica's handler add before the engine: the
client's time from send to first token less the engine's own, request by
request (the recorder's request ring, joined on the client's id); the median.
It moves the time to first token, which no bound could hold as an end-to-end
metric (see ``ttft_p95_ms_steady``): ``MOVES`` names the one the cell keeps."""
import statistics

LAYER = "Serve proxy and router"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "program_span"


def read(facts: dict):
    engine = {r["cid"]: r for r in facts["engine"]["requests"] if r.get("ttft_ms") is not None}
    diffs = [(c["first"] - c["sent"]) * 1e3 - engine[c["cid"]]["ttft_ms"]
             for c in facts["client"]["requests"] if c["ok"] and c["cid"] in engine]
    return statistics.median(diffs) if diffs else None
