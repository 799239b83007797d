"""Model step: device time of the operations under the step programs'
``kv_read`` and ``kv_write`` scopes (reading a layer's K/V pages out of
the pool, writing the step's K/V into it), per execution of the
prefill or decode program in the traced window."""
from bench import trace_reduce
from bench.metrics import _program
from bench.metrics._util import DECODE_MODULE, PREFILL_MODULE

SCOPES = {"kv_read", "kv_write"}


def read(run, name):
    if run.trace is None:
        return None
    stacks = _program.tf_ops(run)
    ns = 0
    for chip, op, s, e in run.trace["op_events"]:
        stack = stacks.get(chip, {}).get(op)
        if stack and SCOPES & set(stack.split("/")) \
                and not trace_reduce.CONTAINER.search(op):
            ns += e - s
    runs = sum(m in (PREFILL_MODULE, DECODE_MODULE)
               for _, m, _, _ in run.trace["modules"])
    return ns / runs / 1e6 if ns and runs else None
