"""A cell's deployment: the service's configuration, the per-core channel
state, the tenants' class banks and the pool of request payloads.

Everything the service is fed is made here from ``--seed``; the plain
reference (`reference.py`) regenerates the same banks and payloads with the
same functions, so it takes nothing that the service made.
"""
from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

from spec import BENCH_DIR

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
WORD = 32


def sizes(conf: dict, rehearse: bool) -> dict:
    """The service block of the configuration, with the rehearsal's toy
    sizes laid over it when ``rehearse``; plus the harness's own knobs."""
    s = dict(conf["service"])
    s.update(conf["harness"])
    if rehearse:
        s.update(conf["rehearsal"])
    return s


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for one named stream of ``seed`` (any whole number,
    larger than 32 bits too): a raw threefry key."""
    ss = np.random.SeedSequence([stream, seed])
    return ss.generate_state(2, dtype=np.uint32)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([stream, seed]))


STREAM_BANKS, STREAM_PAYLOADS, STREAM_TRAFFIC, STREAM_KEYS, STREAM_CHECK = range(5)


def _program_digest() -> str:
    """Digest of the program's sources that the precharacterization runs
    (EM channel, OTA phase search, the scale-out entry, the channel state),
    so that a file written by other code is never read back."""
    from repro.core import em, ota, scaleout
    from repro.phy import channel

    h = hashlib.sha256()
    for mod in (em, ota, scaleout, channel):
        with open(mod.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def chanstate_path(s: dict, cache_dir: str = CACHE_DIR) -> str:
    """The file of a configuration's channel state, named by what the
    precharacterization depends on: the transmitters, the cores, the
    operating point and the program's code (configurations that share a link
    share the file)."""
    link = {k: s[k] for k in ("m_tx", "n_rx_cores", "snr_db")}
    link["code"] = _program_digest()
    digest = hashlib.sha256(json.dumps(link, sort_keys=True).encode()).hexdigest()
    return os.path.join(
        cache_dir, f"chanstate-{s['m_tx']}tx-{s['n_rx_cores']}rx-{digest[:12]}.npz")


def channel_state(s: dict, cache_dir: str = CACHE_DIR):
    """Per-core link state from the paper's offline precharacterization (EM
    channel + OTA phase search on the host), read from its file under
    ``bench/.cache`` when an earlier run wrote it. Returns (ChannelState on
    the host, seconds, whether the file was read)."""
    import jax

    from repro import phy
    from repro.core import scaleout

    path = chanstate_path(s, cache_dir)
    t0 = time.perf_counter()
    fields = ("ber", "valid", "h", "phase_idx", "symbols", "c0", "c1", "n0")
    if os.path.exists(path):
        with np.load(path) as z:
            state = phy.ChannelState(*(np.asarray(z[f]) for f in fields))
        return state, time.perf_counter() - t0, True
    cfg = scaleout.ScaleOutConfig(m_tx=s["m_tx"], n_rx_cores=s["n_rx_cores"],
                                  snr_db=s["snr_db"])
    with jax.default_device(jax.devices("cpu")[0]):
        st = jax.block_until_ready(scaleout.precharacterize_state(cfg))
    state = phy.ChannelState(*(np.asarray(getattr(st, f)) for f in fields))
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, **{f: getattr(state, f) for f in fields})
    os.replace(tmp, path)
    return state, time.perf_counter() - t0, False


def service_config(s: dict, *, noise_planes: int | None = None):
    """The ScaleOutConfig the cell serves (``noise_planes`` overrides the
    configuration's precision: the control's lower-precision path)."""
    from repro.core import scaleout

    return scaleout.ScaleOutConfig(
        n_classes=s["n_classes"], dim=s["dim"], m_tx=s["m_tx"],
        n_rx_cores=s["n_rx_cores"], snr_db=s["snr_db"],
        batch=s["trials_per_request"], representation=s["representation"],
        collective=s["collective"], channel=s["channel"], noise=s["noise"],
        noise_planes=s["noise_planes"] if noise_planes is None else noise_planes,
    )


def make_banks(seed: int, s: dict):
    """Every tenant's packed class bank [T, C, d/32] uint32, on the device in
    one jitted call: each bit a fair coin."""
    import jax
    import jax.numpy as jnp

    key = jnp.asarray(seed_words(seed, STREAM_BANKS))
    shape = (s["tenants"], s["n_classes"], s["dim"] // WORD)
    return jax.jit(lambda k: jax.random.bits(k, shape, jnp.uint32))(key)


def make_payloads(seed: int, s: dict, banks, model_size: int):
    """The payload pool: for each tenant, ``payloads_per_tenant`` trial
    batches, each trial the M class hypervectors that the M transmitters
    send. One jitted call; returns (classes [T, P, B, M] int32, queries
    [T, P, B, S_tx, e_per, d/32] uint32) in the service's query layout (TX g
    sits in column g // e_per; unused encoder slots are zero and abstain)."""
    import jax
    import jax.numpy as jnp

    t, p, b, m = (s["tenants"], s["payloads_per_tenant"],
                  s["trials_per_request"], s["m_tx"])
    c, w = s["n_classes"], s["dim"] // WORD
    e_per = -(-m // model_size)
    key = jnp.asarray(seed_words(seed, STREAM_PAYLOADS))

    def build(k, banks):
        cls = jax.random.randint(k, (t, p, b, m), 0, c, jnp.int32)
        q = jax.vmap(lambda bank, cl: bank[cl])(banks, cls)   # [T, P, B, M, W]
        pad = jnp.zeros((t, p, b, model_size * e_per - m, w), jnp.uint32)
        q = jnp.concatenate([q, pad], axis=3)
        return cls, q.reshape(t, p, b, model_size, e_per, w)

    return jax.jit(build)(key, banks)


class KeyStream:
    """Per-request PHY keys, drawn on the host in bulk from the seed: request
    number i gets row i, whatever the traffic's timing."""

    def __init__(self, seed: int, block: int = 4096):
        self._rng = host_rng(seed, STREAM_KEYS)
        self._block = block
        self._keys = np.zeros((0, 2), np.uint32)

    def __getitem__(self, i: int) -> np.ndarray:
        while i >= len(self._keys):
            more = self._rng.integers(0, 2**32, (self._block, 2), dtype=np.uint32)
            self._keys = np.concatenate([self._keys, more])
        return self._keys[i]
