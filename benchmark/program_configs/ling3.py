"""The program's config of a chip's share, from a configuration's file.

As ``program_configs/qwen3_next.py``: in a file of ``configs/`` the key
``num_experts`` counts the experts held on this chip (it is listed under
``reduced``) and ``num_experts_published`` is the router's width, where
``BailingHybridConfig`` keeps the public config's meaning: ``num_experts``
is what the router chooses among, ``experts_held = (first, count)`` what
this chip computes.
"""


def config(*, num_experts, num_experts_published, experts_held, **published):
    from paddle_hackathon_tpu.models import BailingHybridConfig
    first, count = experts_held
    if count != num_experts:
        raise ValueError(f"num_experts {num_experts} counts the experts "
                         f"held, experts_held says {count}")
    return BailingHybridConfig(num_experts=num_experts_published,
                               experts_held=(first, count), **published)
