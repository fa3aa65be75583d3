"""DeepSeek-V2 236B as one chip's share of its 8-way expert-parallel
layout: ``deepseek-v2-236b`` with 5 layers (the dense one and 4 MoE; the
other 55 lie on further pipeline stages) and, in each MoE layer, routing
group 0 held: experts 0-19 of 160.

Group-limited routing draws each token's experts from at most 3 of the 8
groups, one group per device (arXiv:2405.04434 §2.2), so 8 chips each hold
one group. The router keeps its 160 outputs and routes every token over
all of them; this chip computes the held experts' part and the shared
experts, and nothing stands in for the other chips or their exchange.
"""
import dataclasses

from repro.configs.deepseek_v2_236b import CONFIG as FULL

CONFIG = dataclasses.replace(
    FULL, name="deepseek-v2-236b-ep8", num_layers=5,
    moe=dataclasses.replace(FULL.moe, held=(0, 20)))
