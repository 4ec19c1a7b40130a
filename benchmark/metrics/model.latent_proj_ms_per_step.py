"""Per step, device time of what latent attention adds to a plain layer's
projections, outside remat's second forward and outside the kernels: the
joint down-projection, the latent's norm, the up-projection, the rotation
of the rope parts and the concatenations (scope ``latent_proj`` inside
``attn_latent``; class ``latent_proj`` of ``benchmark/scopes/<family>.py``).
None where the family has no such class."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "latent_proj")
