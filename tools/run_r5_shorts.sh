#!/bin/bash
# Round-5 short on-chip measurements, in priority order, one log each.
# Usage: tools/run_r5_shorts.sh [logdir]   (default /tmp/r5_shorts)
set -u
cd "$(dirname "$0")/.."
LOG=${1:-/tmp/r5_shorts}
mkdir -p "$LOG"

echo "== N-Queens on chip =="
for N in 15 16 17; do
  timeout 900 python -m tpu_tree_search nqueens -N $N --chunk 4096 \
    --capacity $((1 << 22)) > "$LOG/nq$N.log" 2>&1
  tail -4 "$LOG/nq$N.log"
done

echo "== Discovery mode (-u 0) ta030 LB2 =="
rm -f /tmp/tts_ta030_lb2.*
TTS_UB=inf TTS_LB=2 TTS_CHUNK=65536 TTS_BUDGET_S=1200 TTS_SEG=2000 \
  TTS_CKPT_EVERY=50 TTS_CAMPAIGN_OUT="$LOG/discovery.jsonl" \
  timeout 1500 python -u tools/run_campaign.py 30 > "$LOG/ta030_inf.log" 2>&1
tail -2 "$LOG/ta030_inf.log"

echo "== 200x20 / 500x20 rate probes =="
for inst in 101 111; do
  rm -f /tmp/tts_ta${inst}_lb2.*
  TTS_LB=2 TTS_CHUNK=4096 TTS_BUDGET_S=240 TTS_SEG=200 TTS_CKPT_EVERY=1000 \
    TTS_CAMPAIGN_OUT="$LOG/wide.jsonl" \
    timeout 900 python -u tools/run_campaign.py $inst \
    > "$LOG/ta${inst}.log" 2>&1
  tail -2 "$LOG/ta${inst}.log"
done

echo "== LB1 attribution error bar =="
timeout 1200 python tools/validate_attribution.py --iters 30 \
  > "$LOG/attribution.log" 2>&1
tail -4 "$LOG/attribution.log"

echo "== bench.py (final headline) =="
timeout 900 python bench.py > "$LOG/bench.log" 2>&1
cat "$LOG/bench.log"

echo "all shorts done; logs in $LOG"
