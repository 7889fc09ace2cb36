"""Share of the traced window in which the device sat idle inside the
serving replica's own host work: any program ``serve.*`` span (lane
fill, join batch, launch, token fetch, emit), the innermost one at each
idle instant; in percent. Idle time while the client waits for the next
request falls outside these spans."""
from bench import spans


def read(run, ctx):
    got = spans.of_run(run, ctx)
    if got is None:
        return None
    return spans.idle_share(got, [n for n in got["idle"]
                                  if n.startswith("serve.")])
