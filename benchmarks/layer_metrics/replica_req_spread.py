"""DP router: max / mean of the replicas' requests.finished over the window."""


def read(ctx):
    a, b = ctx["after"].get("replicas"), ctx["before"].get("replicas")
    if not a or not b or len(a) != len(b):
        return None
    d = [x["requests"]["finished"] - y["requests"]["finished"]
         for x, y in zip(a, b)]
    mean = sum(d) / len(d)
    return max(d) / mean if mean > 0 else None
