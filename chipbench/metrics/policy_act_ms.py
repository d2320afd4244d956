"""Policy bridge: the program's ``policy_act_batch_ms`` histogram (one
batched D3QL decision per cell and quantum), its mean over the window."""


def read(ctx):
    n = ctx.counter_delta("policy_act_batch_ms.count")
    if n <= 0:
        return None
    return ctx.counter_delta("policy_act_batch_ms.total") / n
