#!/usr/bin/env bash
# Fetch the real MovieLens-1M ratings and point the NCF example at it.
#
# examples/ncf_recommendation.py reads the file named by the ML1M_RATINGS env
# var; without it it falls back to a statistically-matched synthetic dataset
# so everything still runs hermetically on hosts with no network egress.
#
# Usage: scripts/fetch_ml1m.sh [dest-dir]   (default ~/.zoo_datasets)
set -euo pipefail

DEST_ROOT="${1:-$HOME/.zoo_datasets}"
mkdir -p "$DEST_ROOT"
ZIP="$DEST_ROOT/ml-1m.zip"

if [ ! -f "$DEST_ROOT/ml-1m/ratings.dat" ]; then
  curl -fL -o "$ZIP" https://files.grouplens.org/datasets/movielens/ml-1m.zip
  unzip -o "$ZIP" -d "$DEST_ROOT"
  rm -f "$ZIP"
fi

echo "MovieLens-1M ready. Use it with:"
echo "  export ML1M_RATINGS=$DEST_ROOT/ml-1m/ratings.dat"
