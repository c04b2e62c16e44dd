package org.apache.spark.shuffle.tpu;

import java.io.ByteArrayOutputStream;
import java.io.IOException;
import java.util.ArrayList;
import java.util.Iterator;
import java.util.List;

import org.apache.spark.scheduler.MapStatus;
import org.apache.spark.scheduler.MapStatus$;
import org.apache.spark.SparkEnv;
import org.apache.spark.serializer.SerializationStream;
import org.apache.spark.serializer.SerializerInstance;
import org.apache.spark.shuffle.ShuffleWriteMetricsReporter;
import org.apache.spark.shuffle.ShuffleWriter;
import org.apache.spark.storage.BlockManagerId;

import scala.Option;
import scala.Product2;
import scala.collection.JavaConverters;

/**
 * Map-side writer: partitions records with the dependency's partitioner,
 * serializes each bucket with the dependency's serializer, and ships buckets
 * to the daemon in increasing partition order (the staged store enforces the
 * same sequential protocol the reference writer does,
 * NvkvShuffleMapOutputWriter.scala:108) — a batch a frame: the buckets ride
 * together in WritePartition frames of DaemonClient.WRITE_BATCH_BYTES
 * (DaemonClient.writePartitions), as the Python twin's write_partition sends
 * them, and the commit follows the last batch's ack.
 */
public class TpuShuffleWriter<K, V> extends ShuffleWriter<K, V> {
  private final DaemonClient daemon;
  private final TpuShuffleManager.TpuShuffleHandle<K, V, ?> handle;
  /** Daemon map slot: the map task's 0..numMaps-1 partition index. */
  private final int mapIndex;
  /** Spark's mapId as handed to getWriter — the long task attempt id on 3.x,
   * the map index on 2.4; MapStatus is keyed by it either way. */
  private final long mapId;
  private final ShuffleWriteMetricsReporter metrics;
  private long[] partitionLengths;
  private boolean stopped = false;

  public TpuShuffleWriter(
      DaemonClient daemon, TpuShuffleManager.TpuShuffleHandle<K, V, ?> handle,
      int mapIndex, long mapId, ShuffleWriteMetricsReporter metrics) {
    this.daemon = daemon;
    this.handle = handle;
    this.mapIndex = mapIndex;
    this.mapId = mapId;
    this.metrics = metrics;
  }

  @Override
  public void write(scala.collection.Iterator<Product2<K, V>> records) throws IOException {
    int numPartitions = handle.dependency.partitioner().numPartitions();
    SerializerInstance ser = handle.dependency.serializer().newInstance();

    // Bucket serialize: one buffer per partition, then ship in ascending order.
    ByteArrayOutputStream[] buckets = new ByteArrayOutputStream[numPartitions];
    SerializationStream[] streams = new SerializationStream[numPartitions];
    Iterator<Product2<K, V>> it = JavaConverters.asJavaIterator(records);
    while (it.hasNext()) {
      Product2<K, V> rec = it.next();
      int p = handle.dependency.partitioner().getPartition(rec._1());
      if (buckets[p] == null) {
        buckets[p] = new ByteArrayOutputStream();
        streams[p] = ser.serializeStream(buckets[p]);
      }
      streams[p].writeKey(rec._1(), null);
      streams[p].writeValue(rec._2(), null);
      metrics.incRecordsWritten(1);
    }

    int writer = daemon.openMapWriter(handle.shuffleId(), mapIndex);
    List<Integer> ids = new ArrayList<>();
    List<byte[]> blocks = new ArrayList<>();
    long pending = 0;
    for (int p = 0; p < numPartitions; p++) {
      if (buckets[p] == null) continue;
      streams[p].close();
      byte[] data = buckets[p].toByteArray();
      buckets[p] = null;  // the bucket's copy is the frame's from here on
      ids.add(p);
      blocks.add(data);
      pending += data.length;
      metrics.incBytesWritten(data.length);
      if (pending >= DaemonClient.WRITE_BATCH_BYTES) {
        ship(writer, ids, blocks);
        pending = 0;
      }
    }
    ship(writer, ids, blocks);
    partitionLengths = daemon.commitMap(writer);
  }

  /** One WritePartition frame of the pending buckets; empties the lists. */
  private void ship(int writer, List<Integer> ids, List<byte[]> blocks) throws IOException {
    if (ids.isEmpty()) return;
    int[] reduceIds = new int[ids.size()];
    for (int i = 0; i < reduceIds.length; i++) reduceIds[i] = ids.get(i);
    daemon.writePartitions(writer, reduceIds, blocks.toArray(new byte[0][]));
    ids.clear();
    blocks.clear();
  }

  @Override
  public Option<MapStatus> stop(boolean success) {
    if (stopped) return Option.empty();
    stopped = true;
    if (!success || partitionLengths == null) return Option.empty();
    BlockManagerId id = SparkEnv.get().blockManager().shuffleServerId();
    return Option.apply(MapStatus$.MODULE$.apply(id, partitionLengths, mapId));
  }
}
