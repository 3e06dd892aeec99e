"""Per round, the device time of the client-axis collectives (the FSA
parameter all-gather, the int8 all_to_all, any psum_scatter) during which
no other op runs on that device, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t["collective_s"]:
        return None
    return 1000.0 * t["exposed_collective_s"] / ctx["rounds"]
