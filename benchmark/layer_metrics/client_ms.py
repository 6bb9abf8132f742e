"""What the harness itself adds: mean client latency less server_ms
(socket, reply transfer, the client's read)."""


def read(ctx):
    server = ctx["read"]("server_ms")
    if server is None or not ctx["log"]:
        return None
    mean = sum(r["latency"] for r in ctx["log"]) / len(ctx["log"])
    return 1e3 * mean - server
