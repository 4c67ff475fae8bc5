"""Mean host time (us) of QueryEngine.submit (admission and bind) per
query, on the benchmark's clock around the call."""


def read(rec):
    s = rec["submit_s"]
    return sum(s) / len(s) * 1e6 if s else None
