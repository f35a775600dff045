"""Share (%) of the judged requests that the front door answered with a
status other than 200. A stream that the load generator itself cut before
any answer came (the close of a closed-loop run) has no status and is not
counted."""
from benchmark import clientstats


def read(rec):
    if "client" not in rec:
        return None
    js = [s for s in clientstats.judged(rec["client"])
          if not (s["cut"] and s["status"] == 0)]
    return 100.0 * sum(s["status"] != 200 for s in js) / max(1, len(js))
