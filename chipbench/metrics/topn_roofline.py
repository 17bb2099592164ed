"""Roofline share of the top-N programs serving open-loop traffic, in %
(see ``chipbench.work.topn_roofline``)."""
from chipbench.work import topn_roofline as read  # noqa: F401
