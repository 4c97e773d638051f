"""``topk_logits`` kernel's share of its roofline (bench/readers.py): the
least time for the logit rows it was given over the summed device time of
its ``topk_logits_tiles`` ops."""
from bench.readers import topk_logits_roofline as read  # noqa: F401
