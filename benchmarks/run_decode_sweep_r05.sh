#!/bin/bash
# Round-5 decode sweep -> benchmarks/decode_{200m,1b}_v5e1_r05.json
# (assembled by collect_decode_r05.py from the per-run JSON lines).
# One process after another on the chip's machine, and nothing else on it
# (other load on the host's cores poisons host-clock timings).
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH=.:${PYTHONPATH:-}
OUT=${1:-/tmp/decode_r05_lines.jsonl}
: > "$OUT"

run() {
  echo "[decode-sweep] $*" >&2
  local before after
  before=$(wc -l < "$OUT")
  python -u examples/decode_benchmark.py "$@" 2>"$OUT.err" \
    | tail -1 >> "$OUT"
  after=$(wc -l < "$OUT")
  if [ "$after" -le "$before" ]; then
    echo "[decode-sweep] FAILED (no output row): $*" >&2
    tail -5 "$OUT.err" >&2
    FAILURES=$((FAILURES + 1))
  fi
}
FAILURES=0

# 200M short context (xla vs pallas on both cache precisions)
run --model 200m --batch-size 8  --prompt-len 128 --new-tokens 256 --decode-attn xla
run --model 200m --batch-size 8  --prompt-len 128 --new-tokens 256 --decode-attn pallas
run --model 200m --batch-size 8  --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant int8 --decode-attn xla
run --model 200m --batch-size 8  --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant int8 --decode-attn pallas
run --model 200m --batch-size 8  --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant w8a8 --decode-attn xla
run --model 200m --batch-size 32 --prompt-len 128 --new-tokens 256 --decode-attn xla
run --model 200m --batch-size 32 --prompt-len 128 --new-tokens 256 --decode-attn pallas
run --model 200m --batch-size 32 --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant w8a8 --decode-attn xla
run --model 200m --batch-size 32 --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant w8a8 --decode-attn pallas
run --model 200m --batch-size 64 --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant w8a8 --decode-attn xla
# 200M long context (the w8a8 static-gate fix target; pallas loses here)
run --model 200m --batch-size 8 --prompt-len 2048 --new-tokens 256 --decode-attn xla
run --model 200m --batch-size 8 --prompt-len 2048 --new-tokens 256 --decode-attn pallas
run --model 200m --batch-size 8 --prompt-len 2048 --new-tokens 256 --kv-quant int8 --weight-quant int8 --decode-attn xla
run --model 200m --batch-size 8 --prompt-len 2048 --new-tokens 256 --kv-quant int8 --weight-quant w8a8 --decode-attn xla
# 1B
run --model 1b --batch-size 8 --prompt-len 128 --new-tokens 256 --decode-attn xla
run --model 1b --batch-size 8 --prompt-len 128 --new-tokens 256 --decode-attn pallas
run --model 1b --batch-size 8 --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant int8 --decode-attn xla
run --model 1b --batch-size 8 --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant int8 --decode-attn pallas
run --model 1b --batch-size 8 --prompt-len 128 --new-tokens 256 --kv-quant int8 --weight-quant w8a8 --decode-attn xla

if [ "$FAILURES" -gt 0 ]; then
  echo "[decode-sweep] $FAILURES config(s) failed — artifact NOT" \
       "assembled (fix and re-run; partial rows are in $OUT)" >&2
  exit 1
fi
python benchmarks/collect_decode_r05.py "$OUT"
