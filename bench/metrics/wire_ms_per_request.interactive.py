"""wire_ms_per_request.interactive (ms): the wire layer's host time per
request that came over the socket: its decode, encode and write spans
(the QUERY frame decoded, the RESULT frame encoded and written), summed
per request and averaged over the traced run's requests."""
WIRE = ("decode", "encode", "write")


def read(run):
    per = []
    for t in run.traces:
        spans = [s for s in t.spans() if s.name in WIRE]
        if any(s.name == "decode" for s in spans):
            per.append(sum(s.duration_s for s in spans))
    return 1e3 * sum(per) / len(per) if per else None
