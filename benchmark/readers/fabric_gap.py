"""fabric_ms.serve: what the fabric adds to a call. The median, over the
requests answered in the window, of the client's wall time of the call less
the seconds spent inside the service method in the rank (both on this
host's monotonic clock): client, pod server, rank pool and shared-memory
ring, there and back."""


def read(ctx):
    gaps = sorted((r["t_recv"] - r["t_send"]) - (r["t_out"] - r["t_in"])
                  for r in ctx["records"] if r["ok"])
    if not gaps:
        return None
    return 1e3 * gaps[len(gaps) // 2]
