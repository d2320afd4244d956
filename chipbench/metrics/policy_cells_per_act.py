"""Policy bridge: the cells one policy act decides, over the window.  The
program's ``policy_act_cells`` counter (each act adds the cells it
decided) over the count of its ``policy_act_batch_ms`` histogram (one per
act); a program without the counter reads None."""


def read(ctx):
    cells = ctx.counter_delta("policy_act_cells")
    acts = ctx.counter_delta("policy_act_batch_ms.count")
    if cells <= 0 or acts <= 0:
        return None
    return cells / acts
