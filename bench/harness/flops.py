"""Matrix-multiply FLOPs of the AutoMDT actor-critic, from its shapes.

A dense layer of a (d_in x d_out) weight costs 2*d_in*d_out FLOPs per row
forward and twice that backward (input and weight gradients). LayerNorm,
activations and the Gaussian head are left out: they are not matmul work.
Nothing is recomputed in the trainer, so nothing recomputed is counted.
"""

from __future__ import annotations


def policy_forward(obs_dim, hidden, act_dim=3, blocks=3):
    return 2 * (obs_dim * hidden + blocks * 2 * hidden * hidden
                + hidden * act_dim)


def value_forward(obs_dim, hidden, blocks=2):
    return 2 * (obs_dim * hidden + blocks * 2 * hidden * hidden + hidden)


def round_flops(obs_dim, hidden, samples, ppo_epochs):
    """One PPO round over ``samples`` (env, step, flow) rows: the rollout's
    policy forward, then ``ppo_epochs`` forward+backward passes (3x the
    forward) of policy and value."""
    pol = policy_forward(obs_dim, hidden)
    val = value_forward(obs_dim, hidden)
    return samples * (pol + ppo_epochs * 3 * (pol + val))
