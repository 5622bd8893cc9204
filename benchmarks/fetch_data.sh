#!/usr/bin/env bash
# Fetch the real benchmark datasets (rcv1_train.binary, epsilon_normalized)
# from the LIBSVM dataset mirror into benchmarks/data/, for --trainFile runs
# on the real files in place of the synthetic stand-ins.
#
# Integrity: this repo is built on an air-gapped machine, so upstream
# sha256 digests cannot be pinned here ahead of time.  Instead:
#   - trust-on-first-use: the first successful download records each file's
#     sha256 into benchmarks/data.sha256 (commit it!); every later fetch
#     verifies against the recorded digest and fails loudly on mismatch.
#   - shape pins: pass the PUBLISHED dataset shapes (rcv1_train.binary:
#     n=20,242 d=47,236; epsilon: n=400,000 d=2,000) as --numFeatures; the
#     loader refuses a file with a larger feature index.
#
# Usage:  bash benchmarks/fetch_data.sh [rcv1|epsilon|all]
set -euo pipefail

HERE="$(cd "$(dirname "$0")" && pwd)"
DATA="$HERE/data"
SUMS="$HERE/data.sha256"
BASE="https://www.csie.ntu.edu.tw/~cjlin/libsvmtools/datasets/binary"
mkdir -p "$DATA"

fetch() {
    local name="$1"           # remote file name (.bz2)
    local out="$DATA/${name%.bz2}"
    if [[ -f "$out" ]]; then
        # verify the DECOMPRESSED file — the one benchmarks actually
        # consume, and the one still around after the .bz2 is deleted
        echo "already present: $out"
        verify "$(basename "$out")"
        return
    fi
    echo "fetching $BASE/$name ..."
    curl -fL --retry 3 -o "$DATA/$name" "$BASE/$name" \
        || wget -O "$DATA/$name" "$BASE/$name"
    echo "decompressing ..."
    bunzip2 -kf "$DATA/$name"
    verify "$(basename "$out")"
    echo "ready: $out  (the .bz2 may be deleted; the digest covers $out)"
}

# Minimum plausible decompressed sizes — a first-use defense independent
# of the download being honest (ADVICE r3: TOFU alone trusts a
# compromised first fetch).  These are deliberately lower bounds, not
# exact pins: this machine is air-gapped, so an exact published byte count
# cannot be confirmed here, and a wrong exact pin would reject good files.
# Truncated/partial downloads (the realistic corruption) fall far below
# these; a same-size wrong file is for the caller to catch against the
# published (n, d) above.
size_pin() {
    local name="$1" bytes="$2"
    local min=0
    case "$name" in
        rcv1_train.binary)   min=8000000    ;;  # full file is tens of MB
        epsilon_normalized)  min=8000000000 ;;  # full file is ~12 GB
    esac
    if (( min > 0 && bytes < min )); then
        echo "size MISMATCH for $name: got $bytes bytes, expected at" \
             "least $min — truncated or wrong file" >&2
        exit 1
    fi
    echo "size ok: $name ($bytes bytes >= $min)"
}

verify() {
    local name="$1"           # decompressed file name
    local got
    size_pin "$name" "$(stat -c%s "$DATA/$name")"
    got="$(sha256sum "$DATA/$name" | cut -d' ' -f1)"
    if grep -q " $name\$" "$SUMS" 2>/dev/null; then
        local want
        want="$(grep " $name\$" "$SUMS" | cut -d' ' -f1)"
        if [[ "$got" != "$want" ]]; then
            echo "sha256 MISMATCH for $name:" >&2
            echo "  recorded $want" >&2
            echo "  got      $got" >&2
            exit 1
        fi
        echo "sha256 ok: $name"
    else
        echo "$got  $name" >> "$SUMS"
        echo "recorded sha256 (trust-on-first-use): $got  $name"
        echo ">> commit $SUMS so later fetches verify against it"
    fi
}

case "${1:-all}" in
    rcv1)    fetch rcv1_train.binary.bz2 ;;
    epsilon) fetch epsilon_normalized.bz2 ;;
    all)     fetch rcv1_train.binary.bz2; fetch epsilon_normalized.bz2 ;;
    *) echo "usage: $0 [rcv1|epsilon|all]" >&2; exit 2 ;;
esac
