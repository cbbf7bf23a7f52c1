"""Advantage actor-critic (the reference's A3C family).

Reference: rl4j-core ``org/deeplearning4j/rl4j/learning/async/a3c/discrete/
A3CDiscreteDense.java`` + ``ActorCriticFactorySeparateStdDense`` and the
async gradient-accumulating worker threads.

TPU-native redesign: the reference's asynchrony exists to keep JVM threads
busy against a slow per-op backend; on TPU the win is the opposite — step
ALL ``numThread`` environments in lockstep (ONE batched logits call per
tick), accumulate n-step rollouts, then one jitted update of the combined
actor-critic loss (policy gradient with advantage + value MSE + entropy
bonus) through the library's Adam updater.  Same estimator as A3C, better
hardware mapping, no lock-free gradient races.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.learning.config import Adam
from deeplearning4j_tpu.rl.mdp import MDP
from deeplearning4j_tpu.rl.policy import Policy, softmax_sample


@dataclasses.dataclass
class A3CConfiguration:
    """Reference: A3CLearningConfiguration fields (nstep etc.)."""
    seed: int = 123
    maxEpochStep: int = 200
    maxStep: int = 20000
    numThread: int = 4          # becomes the rollout batch width
    nstep: int = 8
    gamma: float = 0.99
    learningRate: float = 7e-4
    entropyCoef: float = 0.01
    valueCoef: float = 0.5


def _init_mlp(key, sizes, dtype=jnp.float32):
    params = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        k1, key = jax.random.split(key)
        s = (2.0 / (a + b)) ** 0.5
        params.append({"W": jax.random.normal(k1, (a, b), dtype) * s,
                       "b": jnp.zeros((b,), dtype)})
    return params


def _mlp(params, x):
    for i, p in enumerate(params):
        x = x @ p["W"] + p["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


class ActorCriticSeparate:
    """Separate policy/value MLPs (reference:
    ActorCriticFactorySeparateStdDense).  Built on plain param pytrees so
    the combined loss stays a single pure function; training runs through
    the library's Adam updater (see A3CDiscreteDense._update)."""

    def __init__(self, nIn: int, nOut: int, seed: int = 0, hidden=(64,)):
        ka, kc = jax.random.split(jax.random.PRNGKey(seed))
        self.params = {
            "actor": _init_mlp(ka, (nIn, *hidden, nOut)),
            "critic": _init_mlp(kc, (nIn, *hidden, 1)),
        }

    @staticmethod
    def logits(params, obs):
        return _mlp(params["actor"], obs)

    @staticmethod
    def value(params, obs):
        return _mlp(params["critic"], obs)[..., 0]


class ACPolicy(Policy):
    """Sample (or argmax) from the learned policy (reference:
    policy/ACPolicy.java)."""

    def __init__(self, net: ActorCriticSeparate, seed: int = 0,
                 greedy: bool = False):
        self.net = net
        self.greedy = greedy
        self._rng = np.random.RandomState(seed)

    def nextAction(self, obs) -> int:
        logits = np.asarray(ActorCriticSeparate.logits(
            self.net.params, jnp.asarray(obs, jnp.float32)[None]))[0]
        if self.greedy:
            return int(np.argmax(logits))
        return softmax_sample(self._rng, logits)


class A3CDiscreteDense:
    """Reference: A3CDiscreteDense — here a synchronous batched A2C."""

    def __init__(self, mdp: MDP, conf: Optional[A3CConfiguration] = None,
                 hidden=(64,)):
        self.conf = conf or A3CConfiguration()
        self.mdps: List[MDP] = [mdp] + [mdp.newInstance()
                                        for _ in range(self.conf.numThread - 1)]
        nIn = int(np.prod(mdp.getObservationSpace().shape))
        self.nOut = mdp.getActionSpace().getSize()
        self.net = ActorCriticSeparate(nIn, self.nOut, self.conf.seed, hidden)
        self._rng = np.random.RandomState(self.conf.seed)
        self.stepCount = 0
        self._updater = Adam(self.conf.learningRate)
        self._optState = jax.tree.map(self._updater.init, self.net.params)
        self._obs = [m.reset() for m in self.mdps]
        self._ep_steps = [0] * len(self.mdps)

    @functools.cached_property
    def _update(self):
        c = self.conf
        up = self._updater

        def loss_fn(params, obs, acts, returns):
            logits = ActorCriticSeparate.logits(params, obs)
            values = ActorCriticSeparate.value(params, obs)
            logp = jax.nn.log_softmax(logits)
            chosen = jnp.take_along_axis(logp, acts[:, None], 1)[:, 0]
            adv = returns - values
            policy_loss = -(chosen * jax.lax.stop_gradient(adv)).mean()
            value_loss = (adv ** 2).mean()
            entropy = -(jnp.exp(logp) * logp).sum(-1).mean()
            return policy_loss + c.valueCoef * value_loss \
                - c.entropyCoef * entropy

        @jax.jit
        def update(params, optState, obs, acts, returns, it):
            loss, g = jax.value_and_grad(loss_fn)(params, obs, acts, returns)
            lr = up.currentLr(it, 0)

            def step_leaf(p, gg, st):
                upd, st2 = up.apply(gg, st, lr, it, 0, param=p)
                return p - upd, st2

            flat_p, tree = jax.tree_util.tree_flatten(params)
            flat_g = jax.tree_util.tree_leaves(g)
            flat_s = tree.flatten_up_to(optState)
            out = [step_leaf(p, gg, st)
                   for p, gg, st in zip(flat_p, flat_g, flat_s)]
            new_p = jax.tree_util.tree_unflatten(tree, [o[0] for o in out])
            new_s = jax.tree_util.tree_unflatten(tree, [o[1] for o in out])
            return new_p, new_s, loss

        return update

    def _batched_logits(self, obs_batch: np.ndarray) -> np.ndarray:
        return np.asarray(ActorCriticSeparate.logits(
            self.net.params, jnp.asarray(obs_batch, jnp.float32)))

    def train(self) -> None:
        c = self.conf
        W = len(self.mdps)
        it = 0
        while self.stepCount < c.maxStep:
            # lockstep rollout: ONE batched logits call per tick for all envs
            traj = [([], [], []) for _ in range(W)]   # obs, act, rew
            done_flags = [False] * W
            for _t in range(c.nstep):
                obs_batch = np.stack(self._obs)
                logits = self._batched_logits(obs_batch)
                for i, env in enumerate(self.mdps):
                    if done_flags[i]:
                        continue
                    a = softmax_sample(self._rng, logits[i])
                    reply = env.step(a)
                    traj[i][0].append(self._obs[i])
                    traj[i][1].append(a)
                    traj[i][2].append(reply.getReward())
                    self._obs[i] = reply.getObservation()
                    self._ep_steps[i] += 1
                    self.stepCount += 1
                    # reference semantics: truncate at maxEpochStep
                    if reply.isDone() or self._ep_steps[i] >= c.maxEpochStep:
                        self._obs[i] = env.reset()
                        self._ep_steps[i] = 0
                        done_flags[i] = True

            # bootstrap values for unfinished rollouts in ONE batched call
            boot_vals = np.asarray(ActorCriticSeparate.value(
                self.net.params, jnp.asarray(np.stack(self._obs),
                                             jnp.float32)))
            obs_b, act_b, ret_b = [], [], []
            for i in range(W):
                o, a, r = traj[i]
                if not o:
                    continue
                R = 0.0 if done_flags[i] else float(boot_vals[i])
                for oo, aa, rr in zip(reversed(o), reversed(a), reversed(r)):
                    R = rr + c.gamma * R
                    obs_b.append(oo)
                    act_b.append(aa)
                    ret_b.append(R)
            self.net.params, self._optState, _ = self._update(
                self.net.params, self._optState,
                jnp.asarray(np.stack(obs_b), jnp.float32),
                jnp.asarray(act_b), jnp.asarray(ret_b, jnp.float32), it)
            it += 1

    def getPolicy(self, greedy: bool = True) -> ACPolicy:
        return ACPolicy(self.net, self.conf.seed, greedy=greedy)


class A3CDiscreteDenseAsync(A3CDiscreteDense):
    """True asynchronous A3C: one Python worker thread per environment,
    Hogwild-style updates against the shared parameters.

    Reference: rl4j ``A3CDiscrete`` / ``AsyncLearning`` — worker threads
    roll out against a stale copy of the global network and apply their
    n-step gradients asynchronously (SURVEY.md §2.7).

    For this env-in-the-loop workload async won wall-clock on the CPU
    mesh in an earlier round (183 vs 133 steps/s; today's code on the
    chip: not measured, ``tests/test_rl_async.py`` prints it): each
    policy query must round-trip host<->device before the env can step,
    so latency dominates and worker threads pipeline it (the economics
    that motivated the reference's thread model).  The batched
    synchronous ``A3CDiscreteDense`` remains the default for its
    deterministic, reproducible updates (fixed seeds -> fixed policy; no
    Hogwild scheduling dependence) and because batched steps win wherever
    compute, not dispatch latency, dominates.
    """

    def train(self) -> None:
        import threading
        c = self.conf
        lock = threading.Lock()     # serializes the shared-param update
        self._updates = 0           # optimizer iteration (NOT env steps:
        # Adam bias correction / LR schedules count updates, same as sync)

        def worker(widx: int):
            env = self.mdps[widx]
            rng = np.random.RandomState(c.seed + 1000 * widx)
            obs = env.reset()
            ep_steps = 0
            while True:
                with lock:
                    if self.stepCount >= c.maxStep:
                        return
                    params = self.net.params   # stale snapshot (Hogwild)
                o_l, a_l, r_l = [], [], []
                done = False
                for _ in range(c.nstep):
                    logits = np.asarray(ActorCriticSeparate.logits(
                        params, jnp.asarray(obs[None], jnp.float32)))[0]
                    a = softmax_sample(rng, logits)
                    reply = env.step(a)
                    o_l.append(obs)
                    a_l.append(a)
                    r_l.append(reply.getReward())
                    obs = reply.getObservation()
                    ep_steps += 1
                    if reply.isDone() or ep_steps >= c.maxEpochStep:
                        obs = env.reset()
                        ep_steps = 0
                        done = True
                        break
                R = 0.0 if done else float(np.asarray(
                    ActorCriticSeparate.value(
                        params, jnp.asarray(obs[None], jnp.float32)))[0])
                rets = []
                for rr in reversed(r_l):
                    R = rr + c.gamma * R
                    rets.append(R)
                rets.reverse()
                with lock:
                    # async apply: gradients computed from the stale
                    # snapshot, applied to the CURRENT shared params
                    self.net.params, self._optState, _ = self._update(
                        self.net.params, self._optState,
                        jnp.asarray(np.stack(o_l), jnp.float32),
                        jnp.asarray(a_l), jnp.asarray(rets, jnp.float32),
                        self._updates)
                    self._updates += 1
                    self.stepCount += len(o_l)

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(len(self.mdps))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
