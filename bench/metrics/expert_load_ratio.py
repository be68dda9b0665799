"""How unevenly the router loads the experts: the median, over the steps
dispatched in the window, of the most tokens any expert received in any
MoE layer of the step over what each would receive under even routing,
``real tokens x top_k / experts``. Both counts are the program's: the
step program returns ``expert_tokens_max`` with its sampled tokens and the
step log keeps it (``repro.serving.steplog``). None where the program's
step log keeps no such counter (a program without it) or logged no step
with routed tokens in the window."""
from benchlib.stats import percentile


def read(run):
    try:
        from repro.serving import steplog
    except ImportError:                 # a program without the step log
        return None
    d = run.dims
    even = d.top_k / d.experts
    ratios = [r.expert_tokens_max / (r.real_tokens * even)
              for r in steplog.RECENT.between(run.t0, run.t1)
              if getattr(r, "expert_tokens_max", 0) and r.real_tokens]
    if not ratios:
        return None
    return percentile(ratios, 50)
