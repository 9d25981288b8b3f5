"""Share of the host's time inside ``ServeSession.decode_step`` (the
``serve.decode`` span) in which nothing ran on device 0 and the host was in
no span nested in it: the per-token round trip of the decode loop."""
import scopes


def read(ctx):
    r = scopes.of_view(ctx)
    span = r["program_spans"].get("serve.decode") if r else None
    if not span or not span["s"] or span["idle_s"] is None:
        return None
    return 100.0 * span["idle_s"] / span["s"]
