"""Faults of the Linear Road deployment, planted under the timed path.

Four blind the toll step's rules by handing it an altered copy of the
ring (the plane's own ring and every other read are left as they are);
the fifth alters one field of every Linear Road answer where the gateway
produces it. The general ``altered_answer`` does not reach these reads.
"""
import jax
import jax.numpy as jnp

POS, LANE, SPEED = 5, 3, 0
EXIT_LANE = 4


def _feed(sim, blind):
    """The toll step reads ``blind(ring, t)`` in place of the ring."""
    tolls = sim.tolls
    step, blind = tolls._step, jax.jit(blind)

    def fed(state, ring, t):
        return step(state, blind(ring, t), t)

    tolls._step = fed


def _slots(ring, t):
    """The tick each ring slot holds."""
    h = ring.shape[0]
    return t - (t - jnp.arange(h)) % h


def lav_four_minutes(sim, gw):
    """LAV averages the last 4 minutes before the current one, not 5."""
    def blind(ring, t):
        tick = _slots(ring, t)
        first = t // 2 * 2
        old = (tick >= first - 10) & (tick < first - 8)
        return jnp.where(old[:, None, None], jnp.nan, ring)

    _feed(sim, blind)


def accidents_ignored(sim, gw):
    """No vehicle is ever seen stopped, so no accident is found."""
    def blind(ring, t):
        return ring.at[(t - 3) % ring.shape[0], :, LANE].set(-1.0)

    _feed(sim, blind)


def exit_lane_charged(sim, gw):
    """A vehicle that leaves its segment by the exit lane pays its toll."""
    def blind(ring, t):
        prev = ring[(t - 1) % ring.shape[0], :, LANE]
        return ring.at[(t - 1) % ring.shape[0], :, LANE].set(
            jnp.where(prev == EXIT_LANE, 1.0, prev))

    _feed(sim, blind)


def half_counts(sim, gw):
    """Half of the fleet left out of the segment statistics."""
    def blind(ring, t):
        half = ring.shape[1] // 2
        return ring.at[:, half:, SPEED].set(jnp.nan)

    _feed(sim, blind)


def altered_lr_answer(sim, gw):
    """One field of every Linear Road answer altered where it is produced."""
    for name, field in (("_read_account_balance", "balance"),
                        ("_read_daily_expenditure", "expenditure"),
                        ("_read_toll_notice", "count")):
        orig = getattr(gw, name)

        def alter(params, orig=orig, field=field):
            body = dict(orig(params))
            body[field] += 1
            return body

        setattr(gw, name, alter)


FAULTS = [lav_four_minutes, accidents_ignored, exit_lane_charged, half_counts,
          altered_lr_answer]
