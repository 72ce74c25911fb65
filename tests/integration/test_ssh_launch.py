"""Live execution of the chief's ssh launch path.

Round-2 gap: ``Coordinator.launch_clients`` had only ever run in
``AUTODIST_DEBUG_REMOTE`` print mode. Two tiers close it:

- **exec-shim tier** (runs everywhere): ``ssh``/``scp`` on PATH are
  minimal exec shims, so the coordinator's *generated command lines are
  actually forked* and the remote command string runs under a real
  shell — validating quoting, inline env assignments, the strategy
  scp+rename shipping, worker bring-up, and the fail-fast monitor with
  real processes.
- **real-sshd tier** (skips when no sshd): throwaway host/user keys +
  ``sshd`` on a loopback port, the reference's CI recipe
  (``/root/reference/Jenkinsfile:96-140`` runs ``sshd -p 12345`` in the
  worker container and drives it from the chief's pytest).

The worker discovers the resource spec via ``SYS_RESOURCE_PATH`` (a
forwarded flag, like the reference's shared spec file) — env vars that
are NOT forwarded do not survive a real ssh login, so the test doubles
as a check that everything a worker needs rides the remote command.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from conftest import free_port, shutdown_service

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SSH_SHIM = """#!/bin/bash
# ssh exec shim: strip option flags, run the remote command locally.
echo "ssh $@" >> "$SHIM_LOG"
args=()
while [[ $# -gt 0 ]]; do
  case "$1" in
    -o|-i|-p) shift 2 ;;
    *) args+=("$1"); shift ;;
  esac
done
exec bash -c "${args[*]:1}"
"""

SCP_SHIM = """#!/bin/bash
# scp exec shim: strip flags, copy src -> (host-stripped) dest.
echo "scp $@" >> "$SHIM_LOG"
args=()
while [[ $# -gt 0 ]]; do
  case "$1" in
    -o|-i|-P) shift 2 ;;
    *) args+=("$1"); shift ;;
  esac
done
src="${args[0]}"
dest="${args[1]#*:}"
[[ "$src" == "$dest" ]] && exit 0
exec cp "$src" "$dest"
"""

PROG = textwrap.dedent("""
    import json, os, sys, time
    import numpy as np
    import jax
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', 1)
    sys.path.insert(0, %(repo)r)
    import autodist_tpu as ad

    ROLE = 'worker' if os.environ.get('AUTODIST_WORKER') else 'chief'
    autodist = ad.AutoDist(strategy_builder=ad.strategy.PS(staleness=1))
    np.random.seed(123)
    inputs = np.random.randn(1000).astype(np.float32)
    outputs = (inputs * 3.0 + 2.0 +
               np.random.randn(1000)).astype(np.float32)
    with autodist.scope():
        x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        W = ad.Variable(5.0, name='W')
        b = ad.Variable(0.0, name='b')
        loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
        train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
        sess = autodist.create_distributed_session()
        %(worker_hook)s
        for _ in range(3):
            sess.run(train_op, {x: inputs, y: outputs})
        b_val = float(np.ravel(sess.get_variable_value('b'))[0])
    print('RESULT ' + json.dumps({'role': ROLE, 'b': b_val}), flush=True)
    autodist._coord.barrier('test/done', 2, timeout_s=120.0)
""")


def _write_shims(tmp_path):
    bindir = tmp_path / 'bin'
    bindir.mkdir()
    for name, body in (('ssh', SSH_SHIM), ('scp', SCP_SHIM)):
        p = bindir / name
        p.write_text(body)
        p.chmod(0o755)
    return str(bindir)


def _resource_file(tmp_path, ssh_section=None):
    info = {'nodes': [
        {'address': '127.0.0.1', 'cpus': [0], 'gpus': [0], 'chief': True,
         'network_bandwidth': 100},
        {'address': '127.0.0.2', 'cpus': [0], 'gpus': [0],
         'network_bandwidth': 100}]}
    if ssh_section:
        info['nodes'][1]['ssh_config'] = 'default'
        info['ssh'] = {'default': ssh_section}
    path = tmp_path / 'resources.yml'
    path.write_text(json.dumps(info))   # JSON is valid YAML
    return str(path)


def _chief_env(tmp_path, resource_file, extra_path=None):
    env = dict(os.environ)
    for k in list(env):
        if k.startswith('AUTODIST_'):
            del env[k]
    env['SYS_RESOURCE_PATH'] = resource_file
    env['AUTODIST_COORD_SERVICE_ADDR'] = '127.0.0.1:%d' % free_port()
    # a registry tracing flag: must ride the shipped worker command
    # line (divergent HLO across SPMD hosts deadlocks)
    env['AUTODIST_PP_STASH_LIMIT_MB'] = '1024'
    env['SHIM_LOG'] = str(tmp_path / 'shim.log')
    if extra_path:
        env['PATH'] = extra_path + os.pathsep + env.get('PATH', '')
    return env


def _run_chief(tmp_path, worker_hook='pass', ssh_section=None,
               with_shims=True, timeout=300):
    prog = tmp_path / 'prog.py'
    prog.write_text(PROG % {'repo': REPO, 'worker_hook': worker_hook})
    env = _chief_env(tmp_path, _resource_file(tmp_path, ssh_section),
                     _write_shims(tmp_path) if with_shims else None)
    try:
        return subprocess.run([sys.executable, str(prog)], env=env,
                              capture_output=True, text=True,
                              timeout=timeout)
    finally:
        # a chief that its fail-fast monitor hard-exits shuts nothing down
        shutdown_service(env['AUTODIST_COORD_SERVICE_ADDR'])


def _results(out):
    """Extract RESULT payloads; two processes share one pipe, so lines
    can butt against each other without a separating newline."""
    dec = json.JSONDecoder()
    found, text, pos = [], out.stdout, 0
    while True:
        pos = text.find('RESULT ', pos)
        if pos < 0:
            return found
        obj, end = dec.raw_decode(text[pos + len('RESULT '):])
        found.append(obj)
        pos += len('RESULT ') + end


@pytest.mark.integration
def test_ssh_launch_path_executes(tmp_path):
    """The chief really forks ssh/scp (exec shims), the shipped command
    line brings up the worker, both train, the strategy file is shipped
    via scp + rename."""
    out = _run_chief(tmp_path)
    assert out.returncode == 0, out.stderr[-4000:]
    # both roles' RESULT lines flow through the chief's stdout (the
    # shim-launched worker inherits it)
    results = _results(out)
    assert {r['role'] for r in results} == {'chief', 'worker'}, out.stdout
    for r in results:
        assert abs(r['b']) > 1e-4, r
    log = (tmp_path / 'shim.log').read_text()
    assert 'scp' in log and '127.0.0.2' in log, log
    assert 'AUTODIST_WORKER=127.0.0.2' in log, log
    assert 'AUTODIST_STRATEGY_ID=' in log, log
    assert 'AUTODIST_PP_STASH_LIMIT_MB=1024' in log, log   # forwarded
    assert 'mv -f' in log, log   # atomic strategy placement


@pytest.mark.integration
def test_ssh_launch_monitor_fails_fast(tmp_path):
    """A worker dying mid-run kills the chief via the fail-fast monitor
    (reference coordinator.py:98-110) — with a real forked process, not
    print mode."""
    hook = ("if ROLE == 'worker':\n"
            "            sess.run(train_op, {x: inputs, y: outputs})\n"
            "            os._exit(17)   # simulated crash mid-run")
    t0 = time.time()
    out = _run_chief(tmp_path, worker_hook=hook)
    # monitor hard-exits the chief (os._exit(1)) on worker death
    assert out.returncode == 1, (out.returncode, out.stdout,
                                 out.stderr[-2000:])
    assert time.time() - t0 < 240
    assert 'exited with code 17' in (out.stdout + out.stderr)


HAVE_SSHD = shutil.which('sshd') is not None and \
    shutil.which('ssh') is not None and \
    shutil.which('ssh-keygen') is not None


@pytest.mark.integration
@pytest.mark.skipif(not HAVE_SSHD, reason='sshd/ssh unavailable')
def test_ssh_launch_real_sshd(tmp_path):
    """Full ssh path against a real local sshd with throwaway keys (the
    reference CI recipe). Skips where sshd cannot run."""
    sshdir = tmp_path / 'sshd'
    sshdir.mkdir()
    hostkey = sshdir / 'host_key'
    userkey = sshdir / 'user_key'
    for key in (hostkey, userkey):
        subprocess.run(['ssh-keygen', '-q', '-t', 'ed25519', '-N', '',
                        '-f', str(key)], check=True)
    auth = sshdir / 'authorized_keys'
    auth.write_text(userkey.with_suffix('.pub').read_text())
    auth.chmod(0o600)
    port = free_port()
    cfg = sshdir / 'sshd_config'
    cfg.write_text(textwrap.dedent("""
        Port %d
        ListenAddress 127.0.0.2
        HostKey %s
        PidFile %s/sshd.pid
        AuthorizedKeysFile %s
        StrictModes no
        UsePAM no
        PasswordAuthentication no
        PermitRootLogin yes
    """ % (port, hostkey, sshdir, auth)))
    sshd = subprocess.Popen([shutil.which('sshd'), '-D', '-f', str(cfg),
                             '-E', str(sshdir / 'sshd.log')])
    try:
        probe = None
        for _ in range(50):
            probe = subprocess.run(
                ['ssh', '-i', str(userkey), '-p', str(port),
                 '-o', 'StrictHostKeyChecking=no',
                 '-o', 'UserKnownHostsFile=/dev/null',
                 '127.0.0.2', 'true'], capture_output=True, timeout=20)
            if probe.returncode == 0:
                break
            time.sleep(0.2)
        if probe is None or probe.returncode != 0:
            pytest.skip('local sshd not usable: %s'
                        % probe.stderr.decode()[-500:])
        out = _run_chief(tmp_path, with_shims=False,
                         ssh_section={'key_file': str(userkey),
                                      'port': port})
        assert out.returncode == 0, (out.stdout, out.stderr[-4000:])
        results = _results(out)
        # over real ssh the worker's stdout flows back through the ssh
        # client the chief holds open
        assert {r['role'] for r in results} == {'chief', 'worker'}, \
            out.stdout
    finally:
        sshd.terminate()
