#!/bin/bash
# Chaos matrix: the vanilla-HiPS demo (12 processes, 3 parties) run
# under ten representative seeded fault plans. Every random decision
# is drawn from PS_SEED-derived streams (geomx_tpu/ps/faults.py), so a
# failing case reproduces exactly by re-running with the same seed.
# The resender is always on: the point of each case is that training
# still completes despite the injected faults.
#
# Cases:
#   loss        20% data-frame drop on every link
#   wan-jitter  added latency + jitter on half the frames, 5% duplicates
#   partition   server id 8 cut off from everyone for 3s mid-run
#   overlap     pipelined round under drops + reordering + duplicates
#   quant-wire  2-bit quantized combined wire (error-feedback residuals
#               on every leg) under drops + duplicates; sanitizer on
#   dist-sync-mesh  mesh-party tier: int8 quantized ring intra-party +
#               2-bit quantized van; party A's server killed mid-run,
#               ring residuals must reset and the sanitizer stay silent
#   shaped-16p  16 in-process parties on the heterogeneous WAN plan
#               (scripts/shapes/hetero16.json): thin-party stragglers,
#               one flapping party server, asymmetric per-link 2-bit
#               codecs on the thin legs; the wire sanitizer audits
#               every van and any violation marker fails the case
#   shaped-16p-health  same 16-party topology with the health plane on
#               (docs/observability.md): a faulted run must raise
#               straggler + link-degradation anomalies naming the
#               planned culprits, then a clean run must raise ZERO
#               anomaly events — detectors that cry wolf fail the case
#   worker-kill both data parties' worker 0 crashes at round 3; elastic
#               membership resizes the round to the survivors
#   server-kill party A's server crashes mid-round; survivors keep
#               training and a respawned server restores the snapshot
#
# Usage: ./run_chaos_matrix.sh [extra worker args...]
#   PS_SEED=<n> picks the schedule (default 7).
cd "$(dirname "$0")"
SEED=${PS_SEED:-7}
FAILED=0
ARTIFACTS=""
CASE_DIRS=()

# On a failed case, gather everything a post-mortem needs into one
# directory: the flight-recorder dumps (last wire events per van), the
# per-round telemetry snapshots, and the process logs — /tmp/hips_*.log
# is overwritten by the NEXT case, so they must be copied now.
collect_artifacts() {
  local name="$1" fdir="$2" tdir="$3"
  [ -z "$ARTIFACTS" ] && ARTIFACTS=$(mktemp -d /tmp/chaos_artifacts.XXXXXX)
  local dest="$ARTIFACTS/$name"
  mkdir -p "$dest"
  cp "$fdir"/flightrec_*.json "$dest"/ 2>/dev/null
  cp "$tdir"/metrics_round*.json "$dest"/ 2>/dev/null
  cp /tmp/hips_*.log "$dest"/ 2>/dev/null
  echo "=== chaos[$name] artifacts: $dest ==="
}

run_case() {
  local name="$1" plan="$2" port_base="$3"; shift 3
  echo "=== chaos[$name] seed=$SEED ==="
  # per-case flight-recorder/telemetry dirs (collected on failure;
  # removed at the end of a fully green matrix)
  LAST_FDIR=$(mktemp -d) LAST_TDIR=$(mktemp -d)
  CASE_DIRS+=("$LAST_FDIR" "$LAST_TDIR")
  (
    export PS_SEED=$SEED
    export PS_FAULT_PLAN="$plan"
    # retransmit layer: short timeout so drops heal fast, an overall
    # delivery deadline so a wedged run fails loudly instead of hanging
    export PS_RESEND=1 PS_RESEND_TIMEOUT=500 PS_RESEND_DEADLINE=120
    export GEOMX_FLIGHTREC_DIR=$LAST_FDIR
    export GEOMX_TELEMETRY=1 GEOMX_TELEMETRY_DIR=$LAST_TDIR
    # distinct ports per case: no TIME_WAIT clashes between cases
    export GPORT=$port_base CPORT=$((port_base + 1)) \
           APORT=$((port_base + 2)) BPORT=$((port_base + 3))
    source ./hips_env.sh
    # || exit 1: a bare `wait` always returns 0, so the subshell's
    # status must come from the foreground worker itself
    launch_hips "$REPO_DIR/examples/cnn.py" --cpu "$@" || exit 1
    wait
  )
  if [ $? -eq 0 ]; then
    echo "=== chaos[$name] OK ==="
  else
    echo "=== chaos[$name] FAILED (re-run with PS_SEED=$SEED to reproduce) ==="
    collect_artifacts "$name" "$LAST_FDIR" "$LAST_TDIR"
    FAILED=1
  fi
}

run_case loss \
  '[{"type": "drop", "p": 0.2}]' \
  9490 "$@"

run_case wan-jitter \
  '[{"type": "delay", "delay_s": 0.02, "jitter_s": 0.03, "p": 0.5},
    {"type": "dup", "p": 0.05}]' \
  9590 "$@"

run_case partition \
  '[{"type": "partition", "between": [8, "*"], "start_s": 5.0, "duration_s": 3.0}]' \
  9690 "$@"

# pipelined round (async chunked push_pull, P3 slicing) under drops,
# reordering and duplicates: chunk responses land out of order and some
# retransmit; training must still complete with the same convergence.
# The wire AND lock sanitizers ride along on this case (no kills, so
# membership never churns): every van checks requests ack exactly once,
# countdowns drain, epochs stay monotone, and every traced lock feeds
# the witness (order inversions, blocking under a lock, @guarded_by
# locksets) — any violation of either fails the case below.
export P3_SLICE_BYTES=131072 GEOMX_WIRE_SANITIZER=1 \
       GEOMX_LOCK_SANITIZER=1
run_case overlap \
  '[{"type": "drop", "p": 0.1},
    {"type": "reorder", "window": 4},
    {"type": "dup", "p": 0.05}]' \
  9790 "$@"
unset P3_SLICE_BYTES GEOMX_WIRE_SANITIZER \
      GEOMX_LOCK_SANITIZER
# launch_hips overwrites /tmp/hips_*.log per case, so these are the
# overlap run's logs
if grep -l "WIRE-SANITIZER VIOLATION" /tmp/hips_*.log 2>/dev/null; then
  echo "=== chaos[overlap] FAILED: wire-sanitizer violations (see logs above) ==="
  # the sanitizer also triggered flight-recorder dumps — collect them
  collect_artifacts overlap-sanitizer "$LAST_FDIR" "$LAST_TDIR"
  FAILED=1
fi
if grep -l "LOCK-SANITIZER VIOLATION" /tmp/hips_*.log 2>/dev/null; then
  echo "=== chaos[overlap] FAILED: lock-sanitizer violations (see logs above) ==="
  collect_artifacts overlap-locksan "$LAST_FDIR" "$LAST_TDIR"
  FAILED=1
fi

# quantized combined wire under loss: every push leg carries 2-bit
# error-feedback codes (the codec rides the async chunked rounds, so
# the pipelined-round knobs come along). Retransmits must replay the
# packed bytes as-sent — a retry that re-drained the residual stream
# would corrupt the error feedback — so the bar is the same as overlap:
# training completes AND the wire sanitizer stays silent.
export GEOMX_WIRE_CODEC=2bit
export P3_SLICE_BYTES=131072 GEOMX_WIRE_SANITIZER=1
run_case quant-wire \
  '[{"type": "drop", "p": 0.1},
    {"type": "dup", "p": 0.05}]' \
  10090 "$@"
unset GEOMX_WIRE_CODEC P3_SLICE_BYTES GEOMX_WIRE_SANITIZER
if grep -l "WIRE-SANITIZER VIOLATION" /tmp/hips_*.log 2>/dev/null; then
  echo "=== chaos[quant-wire] FAILED: wire-sanitizer violations (see logs above) ==="
  collect_artifacts quant-wire-sanitizer "$LAST_FDIR" "$LAST_TDIR"
  FAILED=1
fi

# shaped 16-party chaos (in-process): the link-shaping layer
# (ps/shaping.py) composed with stragglers, a flapping party server
# and asymmetric per-link codecs, sanitizer on. tools/chaos_sim.py
# scales the matrix past the 12-process ceiling — 16-64 parties run as
# threads in ONE process — and exits non-zero on any sanitizer marker
# or incomplete worker, so run_case's plumbing isn't needed here.
echo "=== chaos[shaped-16p] seed=$SEED ==="
if PS_SEED=$SEED JAX_PLATFORMS=cpu \
     ${PYTHON:-python} "$(pwd)/../tools/chaos_sim.py" \
     --parties 16 --seed "$SEED" \
     --shape "$(pwd)/shapes/hetero16.json"; then
  echo "=== chaos[shaped-16p] OK ==="
else
  echo "=== chaos[shaped-16p] FAILED (re-run with PS_SEED=$SEED to reproduce) ==="
  FAILED=1
fi

# health-plane closed loop on the same shaped 16-party topology:
# chaos_sim --health runs the matrix twice — once with planned thin-
# downlink delays and a control-cut flapping server (the scheduler
# board must raise straggler and link-degradation anomalies naming
# those culprits), then once clean (ZERO anomaly events allowed).
# chaos_sim exits non-zero on a missed detection or a false positive.
echo "=== chaos[shaped-16p-health] seed=$SEED ==="
if PS_SEED=$SEED JAX_PLATFORMS=cpu \
     ${PYTHON:-python} "$(pwd)/../tools/chaos_sim.py" \
     --parties 16 --seed "$SEED" --health \
     --shape "$(pwd)/shapes/hetero16.json"; then
  echo "=== chaos[shaped-16p-health] OK ==="
else
  echo "=== chaos[shaped-16p-health] FAILED (re-run with PS_SEED=$SEED to reproduce) ==="
  FAILED=1
fi

# adaptive transport on the same shaped 16-party topology: the
# self-tuning controller (docs/adaptive-transport.md) drives per-link
# codec + slice decisions from live health estimates while both
# sanitizers audit every van and one shaped uplink is squeezed to
# 5 Mbps mid-run. chaos_sim exits non-zero on any sanitizer marker, an
# aborted round (incomplete worker), or a controller that made no live
# decision.
echo "=== chaos[shaped-16p-adaptive] seed=$SEED ==="
if PS_SEED=$SEED JAX_PLATFORMS=cpu \
     ${PYTHON:-python} "$(pwd)/../tools/chaos_sim.py" \
     --parties 16 --seed "$SEED" --controller \
     --shape "$(pwd)/shapes/hetero16.json"; then
  echo "=== chaos[shaped-16p-adaptive] OK ==="
else
  echo "=== chaos[shaped-16p-adaptive] FAILED (re-run with PS_SEED=$SEED to reproduce) ==="
  FAILED=1
fi

# quantized mesh + quantized van under a remote-server kill
# (dist_sync_mesh): 2 parties x 2-virtual-device meshes, intra-party
# gradients ride the int8 block-scaled ppermute ring
# (GEOMX_MESH_CODEC), the van carries the 2-bit combined wire, and
# party A's server crashes mid-run; a respawned server restores the
# snapshot. The abort path must zero every ring error-feedback
# residual stream (reset_mesh_residuals) before the retried round —
# stale error replaying into the ring would corrupt the feedback
# loop — and the wire sanitizer must stay silent through kill +
# recovery on every node of the mesh topology.
echo "=== chaos[dist-sync-mesh] seed=$SEED ==="
LAST_FDIR=$(mktemp -d) LAST_TDIR=$(mktemp -d)
CASE_DIRS+=("$LAST_FDIR" "$LAST_TDIR")
rm -f /tmp/hips_mesh_*.log /tmp/hips_server_1019[23].log
(
  export PS_SEED=$SEED
  export PS_RESEND=1 PS_RESEND_TIMEOUT=500 PS_RESEND_DEADLINE=120
  export PS_HEARTBEAT_INTERVAL=1 PS_HEARTBEAT_TIMEOUT=3
  export GEOMX_FLIGHTREC_DIR=$LAST_FDIR
  export GEOMX_TELEMETRY=1 GEOMX_TELEMETRY_DIR=$LAST_TDIR
  export PS_SNAPSHOT_DIR=$(mktemp -d) PS_SNAPSHOT_INTERVAL=1
  export GEOMX_MESH_CODEC=int8 GEOMX_WIRE_CODEC=2bit
  export P3_SLICE_BYTES=131072 GEOMX_WIRE_SANITIZER=1
  # scoped via hips_env.sh so ONLY party A's server runs this plan
  # (see the server-kill case below); at=60 recv frames lands a few
  # training rounds in — past init, while the ring residuals are warm
  export CHAOS_PLAN_SERVER_A='[{"type": "crash", "node": 8, "at": 60, "on": "recv", "tier": "local"}]'
  export GPORT=10190 CPORT=10191 APORT=10192 BPORT=10193
  source ./hips_env.sh
  # replacement party-A server: registers after the crash has been
  # declared (mesh workers boot jax, so rounds — and the crash frame —
  # land later than in the host-only topologies)
  ( sleep 30
    env $(echo $GLOBALS) DMLC_ROLE=server \
      DMLC_PS_ROOT_URI=$HOST_A DMLC_PS_ROOT_PORT=$APORT \
      DMLC_NUM_SERVER=1 DMLC_NUM_WORKER=1 \
      $PYTHON -c "import geomx_tpu" > /tmp/hips_mesh_server_A_respawn.log 2>&1
  ) &
  launch_mesh_hips "$REPO_DIR/examples/cnn.py" --cpu "$@" || exit 1
  wait
)
if [ $? -eq 0 ]; then
  echo "=== chaos[dist-sync-mesh] OK ==="
else
  echo "=== chaos[dist-sync-mesh] FAILED (re-run with PS_SEED=$SEED to reproduce) ==="
  collect_artifacts dist-sync-mesh "$LAST_FDIR" "$LAST_TDIR"
  FAILED=1
fi
if grep -l "WIRE-SANITIZER VIOLATION" /tmp/hips_mesh_*.log \
     /tmp/hips_server_1019[23].log 2>/dev/null; then
  echo "=== chaos[dist-sync-mesh] FAILED: wire-sanitizer violations (see logs above) ==="
  collect_artifacts dist-sync-mesh-sanitizer "$LAST_FDIR" "$LAST_TDIR"
  FAILED=1
fi

# elastic membership: both data parties' worker 0 (local id 9) dies at
# the start of training round 3 (cnn.py's kv.notify_round drives the
# at_round trigger; the master worker is also local id 9 but exits
# after init, before any round). Heartbeats declare the corpses dead,
# each party's server re-sizes the round countdown to the survivors,
# and the remaining worker per party completes the full run.
export PS_HEARTBEAT_INTERVAL=1 PS_HEARTBEAT_TIMEOUT=3
# the crashed workers' own kv.wait should give up with the resend
# deadline, not the default 300s op timeout — their exit path is serial
# in this single-host run
export PS_OP_TIMEOUT=120
# the full sanitizer complement rides along — wire (ack exactly once),
# lock (order witness) and state (every declare/adopt/fence must agree
# with the executable membership model in tools/analyze/statemodel.py).
# Membership churn is exactly what the state sanitizer mirrors, so a
# kill case with a silent sanitizer is the strongest conformance run.
export GEOMX_WIRE_SANITIZER=1 GEOMX_LOCK_SANITIZER=1 GEOMX_STATE_SANITIZER=1
run_case worker-kill \
  '[{"type": "crash", "node": 9, "at_round": 3, "tier": "local"}]' \
  9890 "$@"
unset PS_HEARTBEAT_INTERVAL PS_HEARTBEAT_TIMEOUT PS_OP_TIMEOUT
unset GEOMX_WIRE_SANITIZER GEOMX_LOCK_SANITIZER GEOMX_STATE_SANITIZER
for marker in WIRE LOCK STATE; do
  if grep -l "$marker-SANITIZER VIOLATION" /tmp/hips_*.log 2>/dev/null; then
    echo "=== chaos[worker-kill] FAILED: $marker sanitizer violations (see logs above) ==="
    collect_artifacts worker-kill-sanitizer "$LAST_FDIR" "$LAST_TDIR"
    FAILED=1
  fi
done

# elastic membership + durable recovery: party A's server crashes on
# its 50th local data frame (mid-round). Its workers' in-flight rounds
# fail fast once the declaration lands; party B and the global tier
# keep training (the FSA countdown re-sizes to the live parties); a
# replacement server then takes the dead slot (is_recovery) and
# restores party A's state from the snapshot.
echo "=== chaos[server-kill] seed=$SEED ==="
LAST_FDIR=$(mktemp -d) LAST_TDIR=$(mktemp -d)
CASE_DIRS+=("$LAST_FDIR" "$LAST_TDIR")
(
  export PS_SEED=$SEED
  export PS_RESEND=1 PS_RESEND_TIMEOUT=500 PS_RESEND_DEADLINE=120
  export PS_HEARTBEAT_INTERVAL=1 PS_HEARTBEAT_TIMEOUT=3
  export GEOMX_FLIGHTREC_DIR=$LAST_FDIR
  export GEOMX_TELEMETRY=1 GEOMX_TELEMETRY_DIR=$LAST_TDIR
  export PS_SNAPSHOT_DIR=$(mktemp -d) PS_SNAPSHOT_INTERVAL=1
  # all three sanitizers ride the crash + recovery: the state sanitizer
  # mirrors the dead-declaration, the replacement's revival and the
  # survivors' fences through the executable membership model
  export GEOMX_WIRE_SANITIZER=1 GEOMX_LOCK_SANITIZER=1 GEOMX_STATE_SANITIZER=1
  # scoped via hips_env.sh so ONLY party A's server runs this plan — a
  # node/tier match alone also hits party B's server and the global
  # servers' local role (all are local id 8)
  export CHAOS_PLAN_SERVER_A='[{"type": "crash", "node": 8, "at": 50, "on": "recv", "tier": "local"}]'
  export GPORT=9990 CPORT=9991 APORT=9992 BPORT=9993
  source ./hips_env.sh
  # replacement party-A server: registers after the crash has been
  # declared, is handed the dead slot and restores the snapshot
  ( sleep 20
    env $(echo $GLOBALS) DMLC_ROLE=server \
      DMLC_PS_ROOT_URI=$HOST_A DMLC_PS_ROOT_PORT=$APORT \
      DMLC_NUM_SERVER=1 DMLC_NUM_WORKER=2 \
      $PYTHON -c "import geomx_tpu" > /tmp/hips_server_A_respawn.log 2>&1
  ) &
  launch_hips "$REPO_DIR/examples/cnn.py" --cpu "$@" || exit 1
  wait
)
if [ $? -eq 0 ]; then
  echo "=== chaos[server-kill] OK ==="
else
  echo "=== chaos[server-kill] FAILED (re-run with PS_SEED=$SEED to reproduce) ==="
  collect_artifacts server-kill "$LAST_FDIR" "$LAST_TDIR"
  FAILED=1
fi
for marker in WIRE LOCK STATE; do
  if grep -l "$marker-SANITIZER VIOLATION" /tmp/hips_*.log 2>/dev/null; then
    echo "=== chaos[server-kill] FAILED: $marker sanitizer violations (see logs above) ==="
    collect_artifacts server-kill-sanitizer "$LAST_FDIR" "$LAST_TDIR"
    FAILED=1
  fi
done

# a green matrix leaves nothing behind; a red one leaves $ARTIFACTS
[ $FAILED -eq 0 ] && rm -rf "${CASE_DIRS[@]}"

exit $FAILED
