"""The program's config of a chip's share, from a configuration's file.

In a file of ``configs/`` the key that counts the experts, ``num_experts``,
gives how many are held on this chip (it is listed under ``reduced``), and
``num_experts_published`` is the router's width.  ``Qwen3NextConfig`` keeps
the public config's meaning: ``num_experts`` is what the router chooses
among, ``experts_held = (first, count)`` is what this chip computes.  The
file's ``program.config`` names this function in the class's place.
"""


def config(*, num_experts, num_experts_published, experts_held, **published):
    from paddle_hackathon_tpu.models import Qwen3NextConfig
    first, count = experts_held
    if count != num_experts:
        raise ValueError(f"num_experts {num_experts} counts the experts "
                         f"held, experts_held says {count}")
    return Qwen3NextConfig(num_experts=num_experts_published,
                           experts_held=(first, count), **published)
