"""Each deploy of a swap mix is code that no compile cache has seen:
a new fingerprint and a new program text, with the same arithmetic."""
import jax
import numpy as np

from repro.core.registry import ActiveCodeRegistry

from bench.drivers import serve as serve_driver
from bench.drivers import train as train_driver


def _deploy_two(slot, sources):
    b = ActiveCodeRegistry().bind("analyst", slot)
    out = []
    for src in sources:
        dep = b.deploy(src)
        out.append((dep.md5, b.current().fingerprint, b.current().fn))
    return out


def test_train_loss_deploys_differ_in_code_not_in_result():
    (m1, f1, fn1), (m2, f2, fn2) = _deploy_two("train_loss", [
        train_driver.z_loss_source(3e-4, 1001),
        train_driver.z_loss_source(3e-4, 1002)])
    assert m1 != m2 and f1 != f2
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 32)
    t1 = jax.jit(fn1).lower(logits, labels).as_text()
    t2 = jax.jit(fn2).lower(logits, labels).as_text()
    assert t1 != t2
    assert float(fn1(logits, labels)) == float(fn2(logits, labels))


def test_sampler_deploys_differ_in_code_not_in_tokens():
    (m1, f1, fn1), (m2, f2, fn2) = _deploy_two("sampler", [
        serve_driver.ab_sampler_source(0.7, 11),
        serve_driver.ab_sampler_source(0.7, 12)])
    assert m1 != m2 and f1 != f2
    logits = jax.random.normal(jax.random.PRNGKey(0), (6, 50))
    key = jax.random.PRNGKey(3)
    assert jax.jit(fn1).lower(logits, key).as_text() != \
        jax.jit(fn2).lower(logits, key).as_text()
    a, b = np.asarray(fn1(logits, key)), np.asarray(fn2(logits, key))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a[::2], np.argmax(np.asarray(logits), -1)[::2])
